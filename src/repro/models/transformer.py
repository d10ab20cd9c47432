"""Decoder-only LM assembly (dense / moe / hybrid / rwkv / vlm).

One code path for every family: layers are grouped into *super-blocks*
(the lcm of the family's interleave patterns — jamba: 8 = 7 mamba + 1
attention with MoE on odd layers; llama4: 2 = dense+MoE; others: 1) and
scanned with stacked parameters, so the lowered HLO stays compact for
62-layer models and remat applies per super-block.

Activations flow *scattered* over the model ring between layers when ESL
overlap is on (plan.esl_overlap) and *replicated* in the blocking
baseline; every sub-module follows the same convention.

Decode rides the scan CARRY so XLA aliases cache buffers in place; the
same path serves the dense per-slot cache, the kv-seq-sharded cache and
the serving engine's paged pool (``block_tables``), single-device or
inside the engine's ``shard_map`` ring — the cache pytree's sharding is
declared by ``registry.Model.cache_specs``, never inspected here.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import esl
from repro.core.dist import AxisEnv, model_rank
from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models.common import InitCtx, apply_norm, init_norm

Params = Dict[str, Any]


def super_block_size(cfg) -> int:
    if cfg.family == "hybrid":
        return cfg.mamba.attn_every
    if cfg.moe is not None:
        return cfg.moe.moe_every
    return 1


def n_super_blocks(cfg) -> int:
    sb = super_block_size(cfg)
    assert cfg.n_layers % sb == 0, (cfg.name, cfg.n_layers, sb)
    return cfg.n_layers // sb


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_layer(ctx: InitCtx, cfg, plan, layer_idx: int) -> Params:
    """One decoder layer (position ``layer_idx % super_block`` pattern)."""
    p: Params = {}
    if cfg.family == "rwkv":
        p["ln1"] = init_norm(ctx, "ln1", cfg.d_model, cfg.norm)
        p["tmix"] = rwkv_mod.init_time_mix(ctx, cfg, plan)
        p["ln2"] = init_norm(ctx, "ln2", cfg.d_model, cfg.norm)
        p["cmix"] = rwkv_mod.init_channel_mix(ctx, cfg, plan)
        return p
    p["ln1"] = init_norm(ctx, "ln1", cfg.d_model, cfg.norm)
    if cfg.is_attention_layer(layer_idx):
        p["attn"] = attn_mod.init_attention(ctx, cfg, plan)
    else:
        p["mamba"] = mamba_mod.init_mamba(ctx, cfg, plan)
    p["ln2"] = init_norm(ctx, "ln2", cfg.d_model, cfg.norm)
    if cfg.is_moe_layer(layer_idx):
        p["moe"] = moe_mod.init_moe(ctx, cfg, plan)
    else:
        p["mlp"] = mlp_mod.init_mlp(ctx, cfg, plan,
                                    bias=(cfg.norm == "layernorm"
                                          and not cfg.mlp_gated))
    return p


def init_super_block(ctx: InitCtx, cfg, plan) -> Params:
    sb = super_block_size(cfg)
    out: Params = {}
    for j in range(sb):
        with ctx.scope(f"l{j}"):
            out[f"l{j}"] = init_layer(ctx, cfg, plan, j)
    return out


def init_lm(ctx: InitCtx, cfg, plan) -> Params:
    from repro.models.common import stacked_init
    D = cfg.d_model
    p: Params = {}
    if cfg.tie_embeddings:
        p["embed"] = ctx.param("embed", (plan.vocab_padded, D),
                               ("vocab", "embed"), scale=1.0)
    else:
        p["embed_in"] = ctx.param("embed_in", (cfg.vocab_size, D),
                                  ("vocab_rep", "embed_scatter"), scale=1.0)
        p["head"] = ctx.param("head", (D, plan.vocab_padded),
                              ("embed", "vocab"), scale=1.0)
    if cfg.positional == "learned":
        p["pos_embed"] = ctx.param("pos_embed", (cfg.max_seq, D),
                                   ("pos", "embed_scatter"), scale=1.0)
    if cfg.vlm is not None:
        p["projector"] = ctx.param(
            "projector", (cfg.vlm.patch_embed_dim, D),
            ("patches", "embed_scatter"), scale=1.0)
    p["blocks"] = stacked_init(ctx, "blocks", n_super_blocks(cfg),
                               lambda c: init_super_block(c, cfg, plan))
    p["ln_f"] = init_norm(ctx, "ln_f", D, cfg.norm)
    return p


# ---------------------------------------------------------------------------
# embeddings / head / loss (vocab column-parallel; logits never unsharded)
# ---------------------------------------------------------------------------

def embed_tokens(p: Params, tokens: jax.Array, cfg, plan,
                 env: AxisEnv) -> jax.Array:
    """tokens (B,S) -> activations in the plan's convention."""
    scattered = plan.esl_overlap and env.model is not None
    if "embed_in" in p:
        # D-sharded table: local slice lookup, no communication at all —
        # output is natively scattered (feeds the first ag_matmul).
        x = jnp.take(p["embed_in"], tokens, axis=0)
        if not scattered and env.model is not None:
            x = esl.gather_scattered(x, axis=env.model, tp=env.tp)
        return x
    # tied, vocab-sharded: masked local rows + ring combine
    w = p["embed"]
    if env.model is None:
        return jnp.take(w, tokens, axis=0)
    v_loc = w.shape[0]
    r = model_rank(env)
    local = tokens - r * v_loc
    ok = (local >= 0) & (local < v_loc)
    x = jnp.take(w, jnp.clip(local, 0, v_loc - 1), axis=0)
    x = jnp.where(ok[..., None], x, 0)
    if scattered:
        return lax.psum_scatter(x, env.model, scatter_dimension=x.ndim - 1,
                                tiled=True)
    return lax.psum(x, env.model)


def add_positional(p: Params, x: jax.Array, positions: jax.Array, cfg, plan,
                   env: AxisEnv) -> jax.Array:
    if cfg.positional != "learned":
        return x
    scattered = plan.esl_overlap and env.model is not None
    pe = p["pos_embed"]
    if env.model is not None:
        # stored D-sharded: local column slice is this rank's shard
        pass
    emb = jnp.take(pe, positions, axis=0)
    if not scattered and env.model is not None:
        emb = esl.gather_scattered(emb, axis=env.model, tp=env.tp)
    return x + emb.astype(x.dtype)


def lm_logits(p: Params, x: jax.Array, cfg, plan, env: AxisEnv) -> jax.Array:
    """-> (B,S,V_pad/tp) vocab-sharded logits (never materialized full)."""
    w = p["head"] if "head" in p else jnp.swapaxes(p["embed"], 0, 1)
    y = esl.ag_matmul(x, w, axis=env.model, tp=env.tp,
                      overlap=plan.esl_overlap,
                      scattered_in=plan.esl_overlap)
    # mask padded vocab columns
    if env.model is None:
        v_ids = jnp.arange(y.shape[-1])
    else:
        v_loc = y.shape[-1]
        v_ids = model_rank(env) * v_loc + jnp.arange(v_loc)
    y = jnp.where(v_ids < cfg.vocab_size, y,
                  jnp.finfo(jnp.float32).min / 2)
    return y


def sharded_xent(logits: jax.Array, labels: jax.Array, env: AxisEnv,
                 ignore: int = -1) -> Tuple[jax.Array, jax.Array]:
    """Cross-entropy over vocab-sharded logits.  Returns (sum_loss, count).

    logits: (B,S,Vloc); labels: (B,S) global token ids (or `ignore`).
    """
    lg = logits.astype(jnp.float32)
    v_loc = lg.shape[-1]
    # the stabilizer is gradient-neutral (lse(x) = log sum exp(x-m) + m
    # holds for any constant m); pmax has no diff rule, so detach *before*.
    m = jnp.max(lax.stop_gradient(lg), -1)
    if env.model is not None:
        m = lax.pmax(m, env.model)
    se = jnp.sum(jnp.exp(lg - m[..., None]), -1)
    if env.model is not None:
        se = lax.psum(se, env.model)
    lse = jnp.log(se) + m                               # (B,S)
    r = model_rank(env)
    local = labels - r * v_loc
    ok = (local >= 0) & (local < v_loc)
    picked = jnp.take_along_axis(
        lg, jnp.clip(local, 0, v_loc - 1)[..., None], axis=-1)[..., 0]
    picked = jnp.where(ok, picked, 0.0)
    if env.model is not None:
        picked = lax.psum(picked, env.model)
    valid = labels != ignore
    loss = jnp.where(valid, lse - picked, 0.0)
    return jnp.sum(loss), jnp.sum(valid)


# ---------------------------------------------------------------------------
# layer application
# ---------------------------------------------------------------------------

def _norm(pn, x, cfg, plan, env):
    scattered = plan.esl_overlap and env.model is not None
    stats_axis = env.model if scattered else None
    scale = esl.full_vec(pn["scale"], axis=env.model, tp=env.tp,
                         scattered_activations=plan.esl_overlap)
    pl = {"scale": scale}
    if "bias" in pn:
        pl["bias"] = esl.full_vec(pn["bias"], axis=env.model, tp=env.tp,
                                  scattered_activations=plan.esl_overlap)
    return apply_norm(pl, x, cfg.norm, stats_axis_name=stats_axis)


def apply_layer(p: Params, x: jax.Array, *, cfg, plan, env: AxisEnv,
                layer_idx: int, positions: jax.Array, mode: str,
                cache: Optional[Params] = None,
                block_tables: Optional[jax.Array] = None,
                paged_kernel: str = "auto", block_s: int = 0,
                kv_valid_len: Optional[jax.Array] = None,
                layer: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Optional[Params], jax.Array]:
    """Returns (x, new_cache, aux_loss).

    ``layer`` (decode over the paged pool): ``cache`` holds every super
    block's pool stacked and this is the super block's index in it.

    ``mode="chunk_prefill"`` (attention-only stacks) prefills one chunk
    of a partially-resident prompt straight against the paged pool:
    ``cache`` is the pool, ``block_tables`` the request's table and
    ``kv_valid_len`` the resident token count after this chunk.
    """
    aux = jnp.float32(0.0)
    new_cache: Optional[Params] = dict(cache) if cache is not None else None

    if cfg.family == "rwkv":
        st = None
        if cache is not None:
            st = {"shift": cache["shift_t"], "wkv": cache["wkv"]}
        h, st2 = rwkv_mod.time_mix_fwd(
            p["tmix"], _norm(p["ln1"], x, cfg, plan, env),
            cfg=cfg, plan=plan, env=env, state=st)
        x = x + h
        st_c = cache["shift_c"] if cache is not None else None
        h, st_c2 = rwkv_mod.channel_mix_fwd(
            p["cmix"], _norm(p["ln2"], x, cfg, plan, env),
            cfg=cfg, plan=plan, env=env, state=st_c)
        x = x + h
        if cache is not None:
            new_cache = {"shift_t": st2["shift"], "wkv": st2["wkv"],
                         "shift_c": st_c2}
        return x, new_cache, aux

    h_in = _norm(p["ln1"], x, cfg, plan, env)
    if "attn" in p:
        if mode == "decode":
            h, kv = attn_mod.decode_attention(
                p["attn"], h_in, cfg=cfg, plan=plan, env=env,
                cache=cache, positions=positions,
                block_table=block_tables, paged_kernel=paged_kernel,
                block_s=block_s, layer=layer)
            new_cache = kv
        elif mode == "prefill":
            h, kv = attn_mod.prefill_attention(
                p["attn"], h_in, cfg=cfg, plan=plan, env=env,
                positions=positions, cache=cache)
            new_cache = kv
        elif mode == "chunk_prefill":
            h, kv = attn_mod.chunk_prefill_attention(
                p["attn"], h_in, cfg=cfg, plan=plan, env=env,
                positions=positions, cache=cache,
                block_table=block_tables, kv_valid_len=kv_valid_len,
                paged_kernel=paged_kernel)
            new_cache = kv
        elif mode == "verify":
            # speculative verify: flattened per-query tables and
            # per-query valid lengths (see verify_attention)
            h, kv = attn_mod.verify_attention(
                p["attn"], h_in, cfg=cfg, plan=plan, env=env,
                positions=positions, cache=cache,
                block_tables=block_tables, kv_valid_len=kv_valid_len,
                paged_kernel=paged_kernel)
            new_cache = kv
        else:
            h = attn_mod.self_attention(
                p["attn"], h_in, cfg=cfg, plan=plan, env=env,
                positions=positions)
    else:
        st = cache if cache is not None else None
        h, st2 = mamba_mod.mamba_fwd(p["mamba"], h_in, cfg=cfg, plan=plan,
                                     env=env, state=st)
        if cache is not None:
            new_cache = st2
    x = x + h

    h_in = _norm(p["ln2"], x, cfg, plan, env)
    if "moe" in p:
        h, aux = moe_mod.moe_fwd(p["moe"], h_in, cfg=cfg, plan=plan, env=env)
    else:
        h = mlp_mod.mlp_fwd(p["mlp"], h_in, cfg=cfg, plan=plan, env=env)
    x = x + h
    return x, new_cache, aux


def apply_super_block(p: Params, x: jax.Array, *, cfg, plan, env: AxisEnv,
                      positions: jax.Array, mode: str,
                      cache: Optional[Params] = None,
                      block_tables: Optional[jax.Array] = None,
                      paged_kernel: str = "auto", block_s: int = 0,
                      kv_valid_len: Optional[jax.Array] = None,
                      layer: Optional[jax.Array] = None):
    sb = super_block_size(cfg)
    aux_total = jnp.float32(0.0)
    new_cache: Dict[str, Any] = {}
    for j in range(sb):
        cj = cache.get(f"l{j}") if cache is not None else None
        x, cj2, aux = apply_layer(p[f"l{j}"], x, cfg=cfg, plan=plan, env=env,
                                  layer_idx=j, positions=positions,
                                  mode=mode, cache=cj,
                                  block_tables=block_tables,
                                  paged_kernel=paged_kernel,
                                  block_s=block_s,
                                  kv_valid_len=kv_valid_len, layer=layer)
        if cache is not None:
            new_cache[f"l{j}"] = cj2
        aux_total = aux_total + aux
    return x, (new_cache if cache is not None else None), aux_total


def _scatter_cache_updates(cache_st, upd, idx, seq_sharded: bool,
                           block_tables=None):
    """Scatter per-layer decode updates into the stacked cache carry."""
    out = {}
    for lj, u in upd.items():
        c = cache_st[lj]
        if u is None:
            out[lj] = c
            continue
        if "k_new" in u:
            knew, vnew = u["k_new"], u["v_new"]
            pos, mask = u["pos"], u["mask"]
            b_idx = jnp.arange(knew.shape[0])
            if block_tables is not None:
                # head-major pool (n_sb, N, gp, bs, dh): the token at
                # logical position ``pos`` lands in physical block
                # table[b, pos // bs] at offset pos % bs of every head.
                # Inactive slots point at the null block 0 (don't-care
                # writes).  The heads are an index of the write, not a
                # window of it: each update is one (dh,) row of one
                # (block, head) plane, which fits the layout the kernel
                # reads the carry in, so XLA keeps the carry there; a
                # (gp, dh) window spanning heads moves the carry to
                # another layout and converts the whole pool around the
                # layer loop.
                gp, bs_blk = c["k"].shape[2:4]
                blk = jnp.take_along_axis(
                    block_tables, (pos // bs_blk)[:, None], axis=1)
                at = (idx, blk, jnp.arange(gp)[None], (pos % bs_blk)[:, None])

                def put(leaf, rows):
                    return leaf.at[at].set(rows[:, 0].astype(leaf.dtype))
                out[lj] = {"k": put(c["k"], knew), "v": put(c["v"], vnew)}
                if "k_scale_new" in u:
                    # quantized pool: the row's absmax scales land beside
                    # the int8/fp8 values at the same (block, offset)
                    out[lj]["k_scale"] = put(c["k_scale"], u["k_scale_new"])
                    out[lj]["v_scale"] = put(c["v_scale"], u["v_scale_new"])
            elif seq_sharded and c["k"].ndim == 6:
                old_k = c["k"][idx, b_idx, 0, pos]
                old_v = c["v"][idx, b_idx, 0, pos]
                val_k = jnp.where(mask[:, None, None], knew[:, 0], old_k)
                val_v = jnp.where(mask[:, None, None], vnew[:, 0], old_v)
                out[lj] = {
                    "k": c["k"].at[idx, b_idx, 0, pos].set(val_k),
                    "v": c["v"].at[idx, b_idx, 0, pos].set(val_v),
                }
            else:
                out[lj] = {
                    "k": c["k"].at[idx, b_idx, pos].set(knew[:, 0]),
                    "v": c["v"].at[idx, b_idx, pos].set(vnew[:, 0]),
                }
        else:
            # small recurrent states (mamba/rwkv): whole-slice update
            out[lj] = jax.tree.map(
                lambda cs, un: cs.at[idx].set(un.astype(cs.dtype)),
                c, u)
    return out


# ---------------------------------------------------------------------------
# full forward passes
# ---------------------------------------------------------------------------

def forward(params: Params, tokens: jax.Array, *, cfg, plan, env: AxisEnv,
            mode: str = "train",
            positions: Optional[jax.Array] = None,
            cache: Optional[Params] = None,
            patch_embeds: Optional[jax.Array] = None,
            block_tables: Optional[jax.Array] = None,
            paged_kernel: str = "auto",
            block_s: int = 0,
            kv_valid_len: Optional[jax.Array] = None,
            gather_fn=None):
    """Shared forward.  ``gather_fn(subtree_path, subtree)`` applies FSDP
    gathering (injected by the step builder; identity in smoke mode).

    ``mode="chunk_prefill"`` rides the same non-decode scan (the pool
    cache slices through the scan xs and restacks through its ys):
    ``positions`` carry the chunk's absolute offsets, ``block_tables``
    the request's table and ``kv_valid_len`` the post-chunk resident
    length — see :func:`repro.models.attention.chunk_prefill_attention`.

    Returns (logits_sharded, new_cache, aux).
    """
    gather_fn = gather_fn or (lambda path, t: t)
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    emb_p = gather_fn("embed", {k: v for k, v in params.items()
                                if k in ("embed", "embed_in", "head",
                                         "pos_embed", "projector")})
    x = embed_tokens(emb_p, tokens, cfg, plan, env)
    x = x.astype(jnp.dtype(plan.compute_dtype))

    if patch_embeds is not None:
        # vision-stub frontend: precomputed patch embeddings -> projector
        pe = esl.ag_matmul(patch_embeds.astype(x.dtype),
                           emb_p["projector"].astype(x.dtype),
                           axis=env.model, tp=env.tp,
                           overlap=plan.esl_overlap, scattered_in=False)
        if not plan.esl_overlap and env.model is not None:
            pe = esl.gather_scattered(pe, axis=env.model, tp=env.tp)
        x = jnp.concatenate([pe, x], axis=1)
        S = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S), (B, S)) \
            if mode != "decode" else positions
    x = add_positional(emb_p, x, positions if mode != "decode"
                       else positions[:, None], cfg, plan, env)
    x = x.astype(jnp.dtype(plan.compute_dtype))

    n_sb = n_super_blocks(cfg)
    aux_total = jnp.float32(0.0)

    def block_fn(carry, xs):
        xc, auxc = carry
        bp, bc = xs
        bp = gather_fn("block", bp)
        xc, nc, aux = apply_super_block(bp, xc, cfg=cfg, plan=plan, env=env,
                                        positions=positions, mode=mode,
                                        cache=bc,
                                        block_tables=block_tables,
                                        paged_kernel=paged_kernel,
                                        block_s=block_s,
                                        kv_valid_len=kv_valid_len)
        return (xc, auxc + aux), nc

    if plan.remat != "none":
        block_fn = jax.checkpoint(block_fn)

    unroll = n_sb if plan.scan_unroll else 1
    if cache is None:
        (x, aux_total), _ = lax.scan(block_fn, (x, aux_total),
                                     (params["blocks"], None), unroll=unroll)
        new_cache = None
    elif mode == "decode":
        # decode: the cache rides the scan CARRY so XLA's while-loop
        # buffer aliasing keeps updates in place — per token we write
        # only the new KV entries, never the 2*L*S*d cache (§Perf 1b)
        seq_sharded = env.kv_seq_axis is not None

        def dec_body(carry, xs):
            xc, auxc, cache_st = carry
            bp, idx = xs
            bp = gather_fn("block", bp)
            if block_tables is None:
                sl = jax.tree.map(
                    lambda a: lax.dynamic_index_in_dim(a, idx, 0,
                                                       keepdims=False),
                    cache_st)
                layer = None
            else:
                # the paged pool is read in place: attention takes the
                # stacked carry and the layer index, never a slice
                sl, layer = cache_st, idx
            xc, upd, aux = apply_super_block(
                bp, xc, cfg=cfg, plan=plan, env=env, positions=positions,
                mode=mode, cache=sl, block_tables=block_tables,
                paged_kernel=paged_kernel, block_s=block_s, layer=layer)
            cache_st = _scatter_cache_updates(cache_st, upd, idx,
                                              seq_sharded, block_tables)
            return (xc, auxc + aux, cache_st), None

        (x, aux_total, new_cache), _ = lax.scan(
            dec_body, (x, aux_total, cache),
            (params["blocks"], jnp.arange(n_sb)), unroll=unroll)
    elif mode in ("chunk_prefill", "verify"):
        # chunk prefill + speculative verify: like decode, the pool
        # rides the scan CARRY so XLA's while-loop buffer aliasing can
        # keep the per-layer slice -> scatter -> write-back chain in
        # place, instead of the xs->ys stacking (whose separate
        # input/output buffers force a full pool copy per layer)
        def chunk_body(carry, xs):
            xc, auxc, cache_st = carry
            bp, idx = xs
            bp = gather_fn("block", bp)
            sl = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, idx, 0,
                                                   keepdims=False),
                cache_st)
            xc, upd, aux = apply_super_block(
                bp, xc, cfg=cfg, plan=plan, env=env, positions=positions,
                mode=mode, cache=sl, block_tables=block_tables,
                paged_kernel=paged_kernel, block_s=block_s,
                kv_valid_len=kv_valid_len)
            cache_st = jax.tree.map(
                lambda st, u: lax.dynamic_update_index_in_dim(
                    st, u.astype(st.dtype), idx, 0),
                cache_st, upd)
            return (xc, auxc + aux, cache_st), None

        (x, aux_total, new_cache), _ = lax.scan(
            chunk_body, (x, aux_total, cache),
            (params["blocks"], jnp.arange(n_sb)), unroll=unroll)
    else:
        (x, aux_total), new_cache = lax.scan(block_fn, (x, aux_total),
                                             (params["blocks"], cache),
                                             unroll=unroll)

    x = _norm(params["ln_f"], x, cfg, plan, env)
    logits = lm_logits(emb_p, x, cfg, plan, env)
    return logits, new_cache, aux_total
