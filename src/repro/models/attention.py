"""GQA attention under the mapper's stored head layout.

Weights live in the *stored* (padded / duplicated) layout computed by
``repro.compiler.plan.plan_attention``:

* query weights:  (D, Hp, dh)  — Hp stored q heads, padded columns zeroed
* kv weights:     (D, Gp, dh)  — Gp stored kv heads; when ``dup > 1``
  adjacent ranks hold *identical* copies of their shard's kv columns, so
  attention never crosses ranks: every stored q head finds its kv head on
  its own rank (``AttnPlan.q_to_kv_local``).
* output weights: (Hp, dh, D)  — rows of padded q heads zeroed, so padded
  heads contribute exactly nothing.

The QKV projection is issued as ONE streamed matmul (weights concatenated
column-wise) — the LPU's "streamlined" C1 dataflow: a single continuous
weight stream through the ESL ``ag_matmul``.  The core softmax/PV loop is
an online-softmax (flash) chunked scan — the SXE∥VXE overlap of Fig. 3(b).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import esl
from repro.core.dist import AxisEnv, model_rank
from repro.kernels.decode_attention.ops import (paged_decode_attention,
                                                paged_stream_supported,
                                                resolve_paged_kernel)
from repro.kernels.decode_attention.ref import gather_kv_pages
from repro.models.common import InitCtx, apply_rope, big_neg

Params = Dict[str, Any]

# paged_stream_supported / resolve_paged_kernel are re-exported here for
# model-level callers (engine, tests); they live next to the kernel in
# kernels/decode_attention/ops.py so every dispatch site shares ONE
# eligibility rule.


# ---------------------------------------------------------------------------
# init (stored layout)
# ---------------------------------------------------------------------------

def _stored_q_builder(attn, d_model, logical_heads, scale):
    def build(key):
        w = jax.random.normal(key, (d_model, logical_heads, attn.d_head),
                              jnp.float32) * scale
        cols = np.asarray(attn.q_orig, np.int64)
        out = jnp.where((cols >= 0)[None, :, None],
                        jnp.take(w, np.clip(cols, 0, logical_heads - 1), axis=1),
                        0.0)
        return out
    return build


def _stored_kv_builder(attn, d_model, scale):
    def build(key):
        g = max(attn.n_kv_heads, 1)
        w = jax.random.normal(key, (d_model, g, attn.d_head),
                              jnp.float32) * scale
        cols = np.asarray(attn.kv_orig, np.int64)
        return jnp.where((cols >= 0)[None, :, None],
                         jnp.take(w, np.clip(cols, 0, g - 1), axis=1), 0.0)
    return build


def _stored_o_builder(attn, d_model, scale):
    def build(key):
        w = jax.random.normal(key, (attn.n_heads, attn.d_head, d_model),
                              jnp.float32) * scale
        rows = np.asarray(attn.q_orig, np.int64)
        return jnp.where((rows >= 0)[:, None, None],
                         jnp.take(w, np.clip(rows, 0, attn.n_heads - 1), axis=0),
                         0.0)
    return build


def init_attention(ctx: InitCtx, cfg, plan, name: str = "attn") -> Params:
    a = plan.attn
    D = cfg.d_model
    s_in = 1.0 / math.sqrt(D)
    s_out = 1.0 / math.sqrt(max(a.n_heads * a.d_head, 1))
    with ctx.scope(name):
        p: Params = {
            "wq": ctx.param_from("wq", (D, a.hp, a.d_head),
                                 ("embed", "q_heads", "head_dim"),
                                 _stored_q_builder(a, D, a.n_heads, s_in)),
            "wk": ctx.param_from("wk", (D, a.gp, a.d_head),
                                 ("embed", "kv_heads", "head_dim"),
                                 _stored_kv_builder(a, D, s_in)),
            "wv": ctx.param_from("wv", (D, a.gp, a.d_head),
                                 ("embed", "kv_heads", "head_dim"),
                                 _stored_kv_builder(a, D, s_in)),
            "wo": ctx.param_from("wo", (a.hp, a.d_head, D),
                                 ("q_heads", "head_dim", "embed"),
                                 _stored_o_builder(a, D, s_out)),
        }
        if cfg.qkv_bias:
            p["bq"] = ctx.param("bq", (a.hp, a.d_head),
                                ("q_heads", "head_dim"), init="zeros")
            p["bk"] = ctx.param("bk", (a.gp, a.d_head),
                                ("kv_heads", "head_dim"), init="zeros")
            p["bv"] = ctx.param("bv", (a.gp, a.d_head),
                                ("kv_heads", "head_dim"), init="zeros")
    return p


# ---------------------------------------------------------------------------
# projections
# ---------------------------------------------------------------------------

def qkv_proj(p: Params, x: jax.Array, env: AxisEnv, plan
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B,S,D/tp) scattered (ESL) or (B,S,D) full.  One streamed matmul.

    Returns local q (B,S,qpr,dh), k,v (B,S,kpr,dh).
    """
    a = plan.attn
    D = p["wq"].shape[0]
    qpr, kpr, dh = a.q_per_rank, a.kv_per_rank, a.d_head
    wq = p["wq"].reshape(D, qpr * dh)
    wk = p["wk"].reshape(D, kpr * dh)
    wv = p["wv"].reshape(D, kpr * dh)
    w = jnp.concatenate([wq, wk, wv], axis=-1)
    b = None
    if "bq" in p:
        b = jnp.concatenate([p["bq"].reshape(-1), p["bk"].reshape(-1),
                             p["bv"].reshape(-1)])
    y = esl.ag_matmul(x, w, axis=env.model, tp=env.tp,
                      overlap=plan.esl_overlap, b=b)
    B, S = y.shape[0], y.shape[1]
    q, k, v = jnp.split(y, [qpr * dh, (qpr + kpr) * dh], axis=-1)
    return (q.reshape(B, S, qpr, dh), k.reshape(B, S, kpr, dh),
            v.reshape(B, S, kpr, dh))


def out_proj(p: Params, attn_out: jax.Array, env: AxisEnv, plan) -> jax.Array:
    """attn_out: (B,S,qpr,dh) -> (B,S,D/tp) scattered (or full baseline)."""
    a = plan.attn
    B, S = attn_out.shape[0], attn_out.shape[1]
    w = p["wo"].reshape(a.q_per_rank * a.d_head, -1)
    return esl.rs_matmul(attn_out.reshape(B, S, -1), w, axis=env.model,
                         tp=env.tp, overlap=plan.esl_overlap,
                         scatter_out=plan.esl_overlap)


def local_kmap(plan, env: AxisEnv) -> jax.Array:
    """(qpr,) local-kv index per local q head for this rank."""
    table = jnp.asarray(plan.attn.q_to_kv_local)       # (tp, qpr)
    return lax.dynamic_index_in_dim(table, model_rank(env), 0, keepdims=False)


def _expand_kv(k: jax.Array, kmap: jax.Array, qpr: int) -> jax.Array:
    """(B,S,kpr,dh) -> (B,S,qpr,dh) per the local q->kv map."""
    if k.shape[2] == 1:
        return jnp.broadcast_to(k, k.shape[:2] + (qpr,) + k.shape[3:])
    return jnp.take(k, kmap, axis=2)


# ---------------------------------------------------------------------------
# flash (online-softmax) core — the SXE||VXE overlapped dataflow
# ---------------------------------------------------------------------------

def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool,
                    q_offset: Optional[jax.Array] = None,
                    kv_valid_len: Optional[jax.Array] = None,
                    kv_base: int = 0,
                    chunk: int = 512,
                    scale: Optional[float] = None) -> jax.Array:
    """Chunked online-softmax attention.

    q: (B,Sq,H,dh); k,v: (B,Skv,H,dh) (same head count — pre-expanded).
    causal uses absolute positions ``q_offset + i`` vs ``kv_base + j``.
    ``kv_valid_len``: (B,) valid kv length (decode against a ring cache).
    """
    B, Sq, H, dh = q.shape
    Skv = k.shape[1]
    scale = scale or (1.0 / math.sqrt(dh))
    chunk = min(chunk, Skv)
    n_chunks = (Skv + chunk - 1) // chunk
    pad = n_chunks * chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, n_chunks, chunk, H, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, H, dh).transpose(1, 0, 2, 3, 4)

    q32 = q.astype(jnp.float32) * scale
    q_pos = (jnp.arange(Sq) if q_offset is None
             else q_offset[..., None] + jnp.arange(Sq))  # (Sq,) or (B,Sq)

    def body(carry, inputs):
        m, l, acc, cidx = carry
        kb, vb = inputs
        kv_pos = kv_base + cidx * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, kb.astype(jnp.float32))
        neg = big_neg(jnp.float32)
        if causal:
            qp = q_pos if q_pos.ndim == 2 else q_pos[None]
            mask = qp[:, None, :, None] >= kv_pos[None, None, None, :]
            s = jnp.where(mask, s, neg)
        if kv_valid_len is not None:
            ok = kv_pos[None, :] < kv_valid_len[:, None]      # (B, chunk)
            s = jnp.where(ok[:, None, None, :], s, neg)
        if Skv % chunk:
            inb = (cidx * chunk + jnp.arange(chunk)) < Skv
            s = jnp.where(inb[None, None, None, :], s, neg)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p, vb.astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new, cidx + 1), None

    m0 = jnp.full((B, H, Sq), big_neg(jnp.float32), jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, dh), jnp.float32)
    (m, l, acc, _), _ = lax.scan(body, (m0, l0, a0, jnp.int32(0)), (kc, vc))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)     # (B,Sq,H,dh)


# ---------------------------------------------------------------------------
# full layers: self-attention (train/prefill), decode, cross-attention
# ---------------------------------------------------------------------------

def self_attention(p: Params, x: jax.Array, *, cfg, plan, env: AxisEnv,
                   positions: jax.Array, causal: bool = True) -> jax.Array:
    """Training/prefill self-attention.  x scattered or full per plan."""
    a = plan.attn
    q, k, v = qkv_proj(p, x, env, plan)
    if cfg.positional == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    kmap = local_kmap(plan, env)
    k = _expand_kv(k, kmap, a.q_per_rank)
    v = _expand_kv(v, kmap, a.q_per_rank)
    out = flash_attention(q, k, v, causal=causal)
    return out_proj(p, out, env, plan)


def prefill_attention(p: Params, x: jax.Array, *, cfg, plan, env: AxisEnv,
                      positions: jax.Array, cache: Dict[str, jax.Array],
                      causal: bool = True
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Prefill: same as self-attention but fills the KV cache."""
    a = plan.attn
    q, k, v = qkv_proj(p, x, env, plan)
    if cfg.positional == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    S = k.shape[1]
    new_cache = dict(cache)
    new_cache["k"] = _cache_insert_prefix(cache["k"], k, env)
    new_cache["v"] = _cache_insert_prefix(cache["v"], v, env)
    kmap = local_kmap(plan, env)
    ke = _expand_kv(k, kmap, a.q_per_rank)
    ve = _expand_kv(v, kmap, a.q_per_rank)
    out = flash_attention(q, ke, ve, causal=causal)
    return out_proj(p, out, env, plan), new_cache


def chunk_prefill_attention(p: Params, x: jax.Array, *, cfg, plan,
                            env: AxisEnv, positions: jax.Array,
                            cache: Dict[str, jax.Array],
                            block_table: jax.Array,
                            kv_valid_len: jax.Array,
                            paged_kernel: str = "auto"
                            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Prefill ONE chunk of a partially-resident prompt against the pool.

    The entry point behind the engine's ``--prefill-chunk`` interleave:
    instead of one monolithic bucketed prefill, the prompt arrives in
    fixed-size chunks whose KV is written *incrementally* into the
    shared block pool, and each chunk's queries attend to the full
    resident history (earlier chunks, or a preempted request's
    recomputed tokens) plus the causal prefix of the chunk itself.

    x:            (1, C, D[/tp]) the chunk activations (C is static —
                  ONE trace total, vs O(log2 max_seq) pow2 buckets)
    positions:    (1, C) absolute positions ``start + [0..C)``
    cache:        {'k','v': (N, kpr, bs, dh)} the shared block pool
                  (rank-local head shard under ring tp, like decode)
    block_table:  (1, T) this request's physical block ids
    kv_valid_len: scalar — total resident tokens AFTER this chunk
                  (start + valid rows; padded tail rows beyond it are
                  routed to the null block and masked on read).

    Dataflow mirrors decode's ``paged_kernel`` seam:

    * ``"stream"`` — the chunk IS a batch for the paged Pallas kernel:
      C queries with per-query valid lengths ``pos + 1`` share the
      request's (broadcast) block table, so causality falls out of the
      kernel's own length masking and the per-position online-softmax
      fold — the same no-copy KV stream as decode, reused for prefill.
    * ``"gather"`` — reference oracle: materialize the contiguous view
      through the table and run the chunked flash prefill with
      ``q_offset`` carrying the chunk's absolute position.

    Both scatter the chunk's K/V into the pool FIRST (the fold then
    covers self + history through one length mask), and both return the
    full updated pool as the new cache — the scan carry aliases it in
    place, so per chunk only the C new rows are written.
    """
    from repro.serving.kv_cache import quantize_kv_rows, scatter_chunk_rows
    a = plan.attn
    q, k, v = qkv_proj(p, x, env, plan)
    if cfg.positional == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    table = block_table[0]
    pos = positions[0]
    valid = pos < kv_valid_len
    quantized = "k_scale" in cache
    ks = vs = None
    if quantized:
        # quantize at pool-write time; the chunk's own rows are read
        # back dequantized (post-update-read contract), so every later
        # mode sees the SAME stored values this chunk attended
        kq, ksc = quantize_kv_rows(k[0], cache["k"].dtype,
                                   cache["k_scale"].dtype)
        vq, vsc = quantize_kv_rows(v[0], cache["v"].dtype,
                                   cache["v_scale"].dtype)
        kc = scatter_chunk_rows(cache["k"], kq, table, pos, valid)
        vc = scatter_chunk_rows(cache["v"], vq, table, pos, valid)
        ks = scatter_chunk_rows(cache["k_scale"], ksc, table, pos, valid)
        vs = scatter_chunk_rows(cache["v_scale"], vsc, table, pos, valid)
    else:
        kc = scatter_chunk_rows(cache["k"], k[0], table, pos, valid)
        vc = scatter_chunk_rows(cache["v"], v[0], table, pos, valid)
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = kc, vc
    if quantized:
        new_cache["k_scale"], new_cache["v_scale"] = ks, vs

    C = q.shape[1]
    mode = resolve_paged_kernel(plan, paged_kernel)
    if mode == "stream":
        # per-query causal span: history + self (clamped for pad rows)
        lens = jnp.minimum(pos + 1, kv_valid_len)
        tabs = jnp.broadcast_to(table[None], (C, table.shape[0]))
        out = paged_decode_attention(
            q[0], kc, vc, tabs, lens, k_scale=ks, v_scale=vs)[None]
    else:
        kview, vview = _gather_pool_view(kc, vc, ks, vs, table[None])
        kmap = local_kmap(plan, env)
        ke = _expand_kv(kview, kmap, a.q_per_rank)
        ve = _expand_kv(vview, kmap, a.q_per_rank)
        out = flash_attention(q, ke, ve, causal=True, q_offset=pos[:1],
                              kv_valid_len=kv_valid_len[None])
    return out_proj(p, out, env, plan), new_cache


def verify_attention(p: Params, x: jax.Array, *, cfg, plan,
                     env: AxisEnv, positions: jax.Array,
                     cache: Dict[str, jax.Array],
                     block_tables: jax.Array,
                     kv_valid_len: jax.Array,
                     paged_kernel: str = "auto"
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Score a speculative verify window against the block pool.

    Every decode slot's (last committed token + k draft tokens) are
    flattened into ONE batch of single-token queries — the chunk-as-
    batch trick of :func:`chunk_prefill_attention`, generalized to
    per-query block tables so many requests verify in one kernel call.

    x:            (1, Q, D[/tp]) flattened queries, Q = B*(k+1)
    positions:    (1, Q) each query's absolute position
    block_tables: (Q, T) each query's OWN table (a slot's k+1 rows
                  repeat its table; idle slots ride the null block)
    kv_valid_len: (Q,) per-query causal span INCLUDING self
                  (``pos + 1``; clamped >= 1 for idle rows).

    Draft K/V scatters into the pool FIRST (per-query tables via
    :func:`repro.serving.kv_cache.scatter_spec_rows`), then each query
    attends its own length-masked span — so draft i sees drafts < i of
    the same window plus all resident history, exactly the sequential
    decode dataflow.  This is why verify cannot reuse decode's
    pre-update-read contract (the in-kernel fold only covers a query's
    OWN new token, not its window predecessors).  Rejected drafts need
    no undo: their rows land past the accepted resident length, stay
    masked, and are overwritten idempotently by later windows.
    """
    from repro.serving.kv_cache import quantize_kv_rows, scatter_spec_rows
    a = plan.attn
    q, k, v = qkv_proj(p, x, env, plan)
    if cfg.positional == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    pos = positions[0]
    lens = kv_valid_len
    valid = lens > pos
    quantized = "k_scale" in cache
    ks = vs = None
    if quantized:
        kq, ksc = quantize_kv_rows(k[0], cache["k"].dtype,
                                   cache["k_scale"].dtype)
        vq, vsc = quantize_kv_rows(v[0], cache["v"].dtype,
                                   cache["v_scale"].dtype)
        kc = scatter_spec_rows(cache["k"], kq, block_tables, pos, valid)
        vc = scatter_spec_rows(cache["v"], vq, block_tables, pos, valid)
        ks = scatter_spec_rows(cache["k_scale"], ksc, block_tables, pos,
                               valid)
        vs = scatter_spec_rows(cache["v_scale"], vsc, block_tables, pos,
                               valid)
    else:
        kc = scatter_spec_rows(cache["k"], k[0], block_tables, pos, valid)
        vc = scatter_spec_rows(cache["v"], v[0], block_tables, pos, valid)
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = kc, vc
    if quantized:
        new_cache["k_scale"], new_cache["v_scale"] = ks, vs

    mode = resolve_paged_kernel(plan, paged_kernel)
    if mode == "stream":
        out = paged_decode_attention(
            q[0], kc, vc, block_tables, lens, k_scale=ks, v_scale=vs)[None]
    else:
        kview, vview = _gather_pool_view(kc, vc, ks, vs, block_tables)
        kmap = local_kmap(plan, env)
        ke = _expand_kv(kview, kmap, a.q_per_rank)
        ve = _expand_kv(vview, kmap, a.q_per_rank)
        out = flash_attention(q[0][:, None], ke, ve, causal=True,
                              q_offset=pos, kv_valid_len=lens)
        out = out.swapaxes(0, 1)
    return out_proj(p, out, env, plan), new_cache


def decode_attention(p: Params, x: jax.Array, *, cfg, plan, env: AxisEnv,
                     cache: Dict[str, jax.Array], positions: jax.Array,
                     block_table: Optional[jax.Array] = None,
                     paged_kernel: str = "auto",
                     block_s: int = 0,
                     layer: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token generation step against the KV cache.

    ``block_s`` overrides the KV stream chunk of the dense / gathered
    flash-decode path (0 = the default 2048); the streamed paged kernel's
    tile is structurally the pool block size, so the override does not
    apply there (the engine rejects conflicting requests up front).

    x: (B,1,D[/tp]);  positions: (B,) current position of each sequence.
    cache['k'/'v']: local (B, Smax[/kvseq], kpr, dh); cache['len'] == positions
    handled by the caller (engine).  This is the LPU's target regime: one
    activation vector against streamed weights + streamed KV.

    Paged mode (``block_table`` given): cache['k'/'v'] is the shared block
    pool of every layer, stacked (L, N, kpr, bs, dh) (scales (L, N, kpr,
    bs)), and ``layer`` the one this call attends.  Nothing slices the
    layer out: the kernel takes the stack and the layer index, so the
    decode scan's carry is read in place.  ``paged_kernel`` selects the
    dataflow:

    * ``"stream"`` — the Pallas paged kernel streams KV tiles straight
      from the pool via the scalar-prefetched block table; the new
      token's K/V folds into the online-softmax carry in-kernel.  No
      per-request contiguous view is EVER materialized — the paper's
      no-copy decode stream (Fig. 3b).
    * ``"gather"`` — the reference oracle: materialize the contiguous
      (B, T*bs, ...) view through the table, then run the same chunked
      flash decode as the dense cache (an O(resident-tokens) HBM copy
      per layer per step — kept as the bit-trustworthy baseline).
    * ``"auto"`` — stream when the stored GQA layout allows it
      (:func:`paged_stream_supported`), else gather.

    Both modes mask by ``positions`` (null blocks past the valid length
    never contribute) and return the same pre-update cache contract: the
    caller scatters (k_new, v_new) into the pool afterwards.  Under ring
    tp the pool arrives head-sharded (kpr = Gp/tp local heads) with the
    SAME block ids on every rank, so the replicated table drives all
    shards — paged decode composes with the ESL ring, but not with
    kv-seq sharding (the pool's block dim already replaces the seq dim).
    """
    a = plan.attn
    q, k_new, v_new = qkv_proj(p, x, env, plan)
    if cfg.positional == "rope":
        q = apply_rope(q, positions[:, None], cfg.rope_theta)
        k_new = apply_rope(k_new, positions[:, None], cfg.rope_theta)

    kc, vc = cache["k"], cache["v"]
    if block_table is not None:
        assert env.kv_seq_axis is None, \
            "paged KV shards heads over the model ring, not the seq axis"
        mode = resolve_paged_kernel(plan, paged_kernel)
        quantized = "k_scale" in cache
        if quantized:
            from repro.serving.kv_cache import (dequantize_kv,
                                                quantize_kv_rows)
            # quantize FIRST, attend the dequantized round-trip: decode
            # must see the exact value the pool will store, or later
            # reads of this row (verify windows, chunked prefill) would
            # diverge from the step that emitted it
            kq, ksc = quantize_kv_rows(k_new, kc.dtype,
                                       cache["k_scale"].dtype)
            vq, vsc = quantize_kv_rows(v_new, vc.dtype,
                                       cache["v_scale"].dtype)
            k_fold, v_fold = dequantize_kv(kq, ksc), dequantize_kv(vq, vsc)
            updates = {"k_new": kq, "v_new": vq,
                       "k_scale_new": ksc, "v_scale_new": vsc,
                       "pos": positions,
                       "mask": jnp.ones(positions.shape, bool)}
        else:
            k_fold, v_fold = k_new, v_new
            updates = {"k_new": k_new.astype(kc.dtype),
                       "v_new": v_new.astype(vc.dtype),
                       "pos": positions,
                       "mask": jnp.ones(positions.shape, bool)}
        if mode == "stream":
            out = paged_decode_attention(
                q[:, 0], kc, vc, block_table, positions, layer=layer,
                k_new=k_fold[:, 0], v_new=v_fold[:, 0],
                k_scale=cache.get("k_scale"),
                v_scale=cache.get("v_scale"))[:, None]
            return out_proj(p, out, env, plan), updates
        kc, vc = _gather_pool_view(kc, vc, cache.get("k_scale"),
                                   cache.get("v_scale"), block_table, layer)
        kmap = local_kmap(plan, env)
        out = _flash_decode_chunked(q, kc, vc, kmap,
                                    kv_valid_len=positions,
                                    chunk=block_s or 2048,
                                    k_new=k_fold, v_new=v_fold)
        return out_proj(p, out, env, plan), updates
    if env.kv_seq_axis is None:
        # read the cache pre-update; the new token folds into the online
        # softmax and the caller scatters (k_new, v_new) into the scan
        # CARRY in place — no full-cache rewrite per layer (§Perf it. 1b)
        kmap = local_kmap(plan, env)
        out = _flash_decode_chunked(q, kc, vc, kmap,
                                    kv_valid_len=positions,
                                    chunk=block_s or 2048,
                                    k_new=k_new, v_new=v_new)
        updates = {"k_new": k_new.astype(kc.dtype),
                   "v_new": v_new.astype(vc.dtype),
                   "pos": positions,
                   "mask": jnp.ones(positions.shape, bool)}
    else:
        # long-context: KV sequence sharded across `kv_seq_axis`; the
        # global cache is rank-major (B, width, S/width, kpr, dh) and the
        # local shard carries a singleton width dim -- squeeze it here.
        kc_l, vc_l = kc[:, 0], vc[:, 0]
        out = _seq_sharded_decode(q, kc_l, vc_l, k_new, v_new, positions,
                                  plan, env)
        r = lax.axis_index(env.kv_seq_axis)
        s_loc = kc_l.shape[1]
        local_pos = positions - r * s_loc
        mine = (local_pos >= 0) & (local_pos < s_loc)
        updates = {"k_new": k_new.astype(kc.dtype),
                   "v_new": v_new.astype(vc.dtype),
                   "pos": jnp.clip(local_pos, 0, s_loc - 1),
                   "mask": mine}
    return out_proj(p, out, env, plan), updates


def cross_attention(p: Params, x: jax.Array, *, cfg, plan, env: AxisEnv,
                    enc_k: jax.Array, enc_v: jax.Array) -> jax.Array:
    """Decoder cross-attention to (precomputed) encoder K/V (whisper)."""
    a = plan.attn
    # only the query projection of x; enc_k/enc_v already per-head local
    D = p["wq"].shape[0]
    qpr, dh = a.q_per_rank, a.d_head
    wq = p["wq"].reshape(D, qpr * dh)
    bq = p["bq"].reshape(-1) if "bq" in p else None
    q = esl.ag_matmul(x, wq, axis=env.model, tp=env.tp,
                      overlap=plan.esl_overlap, b=bq)
    B, S = q.shape[0], q.shape[1]
    q = q.reshape(B, S, qpr, dh)
    kmap = local_kmap(plan, env)
    ke = _expand_kv(enc_k, kmap, qpr)
    ve = _expand_kv(enc_v, kmap, qpr)
    out = flash_attention(q, ke, ve, causal=False)
    return out_proj(p, out, env, plan)


def encode_cross_kv(p: Params, enc_x: jax.Array, *, cfg, plan, env: AxisEnv
                    ) -> Tuple[jax.Array, jax.Array]:
    """K/V of encoder states for cross-attention (computed once)."""
    a = plan.attn
    D = p["wk"].shape[0]
    kpr, dh = a.kv_per_rank, a.d_head
    wk = p["wk"].reshape(D, kpr * dh)
    wv = p["wv"].reshape(D, kpr * dh)
    w = jnp.concatenate([wk, wv], -1)
    b = (jnp.concatenate([p["bk"].reshape(-1), p["bv"].reshape(-1)])
         if "bk" in p else None)
    y = esl.ag_matmul(enc_x, w, axis=env.model, tp=env.tp,
                      overlap=plan.esl_overlap, b=b)
    B, S = y.shape[0], y.shape[1]
    k, v = jnp.split(y, 2, axis=-1)
    return k.reshape(B, S, kpr, dh), v.reshape(B, S, kpr, dh)


def _flash_decode_chunked(q: jax.Array, k: jax.Array, v: jax.Array,
                          kmap: jax.Array, *, kv_valid_len: jax.Array,
                          chunk: int = 2048,
                          k_new: Optional[jax.Array] = None,
                          v_new: Optional[jax.Array] = None) -> jax.Array:
    """Generation-stage flash attention with ZERO cache materialization.

    §Perf iteration 1 (deepseek x decode_32k): the generic path
    up-converted the whole KV cache to f32 and pre-transposed it into
    chunk-major layout — ~3 full-cache HBM copies per layer.  Here the
    cache is consumed *in place*: chunks are dynamic-sliced from the
    stored (B,S,kpr,dh) layout, dots run on bf16 operands with f32
    accumulation (preferred_element_type), and GQA needs no expansion —
    scores are computed against all kpr local KV heads (kpr<=5) and the
    per-q-head row is selected by the static-shape ``kmap`` gather.

    q: (B,1,qpr,dh); k,v: (B,S,kpr,dh); -> (B,1,qpr,dh).
    """
    B, _, qpr, dh = q.shape
    S, kpr = k.shape[1], k.shape[2]
    chunk = min(chunk, S)
    n_chunks = (S + chunk - 1) // chunk
    scale = 1.0 / math.sqrt(dh)
    qs = (q[:, 0].astype(jnp.float32) * scale).astype(k.dtype)  # (B,qpr,dh)
    sel = kmap[None, :, None, None]                   # (1,qpr,1,1) gather

    def body(carry, cidx):
        m, l, acc = carry
        start = cidx * chunk
        kb = lax.dynamic_slice_in_dim(k, start, chunk, axis=1)
        vb = lax.dynamic_slice_in_dim(v, start, chunk, axis=1)
        # scores vs ALL local kv heads, f32 accumulation, bf16 stream
        s_all = jnp.einsum("bqd,bkgd->bqgk", qs, kb,
                           preferred_element_type=jnp.float32)
        s = jnp.take_along_axis(
            s_all, jnp.broadcast_to(sel, (B, qpr, 1, chunk)),
            axis=2)[:, :, 0]
        pos = start + jnp.arange(chunk)
        ok = pos[None, :] < kv_valid_len[:, None]
        s = jnp.where(ok[:, None, :], s, big_neg(jnp.float32))
        m_new = jnp.maximum(m, jnp.max(s, -1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, -1)
        pv_all = jnp.einsum("bqk,bkgd->bqgd", p.astype(k.dtype), vb,
                            preferred_element_type=jnp.float32)
        pv = jnp.take_along_axis(
            pv_all, jnp.broadcast_to(sel, (B, qpr, 1, dh)), axis=2)[:, :, 0]
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, qpr), big_neg(jnp.float32), jnp.float32)
    l0 = jnp.zeros((B, qpr), jnp.float32)
    a0 = jnp.zeros((B, qpr, dh), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0), jnp.arange(n_chunks))
    if k_new is not None:
        # fold in the just-generated token (the cache is read pre-update;
        # the caller scatters (k_new, v_new) into the carry afterwards)
        s_all = jnp.einsum("bqd,bkgd->bqgk", qs, k_new,
                           preferred_element_type=jnp.float32)
        s_self = jnp.take_along_axis(
            s_all, jnp.broadcast_to(sel, (B, qpr, 1, 1)), axis=2)[:, :, 0, 0]
        m_new = jnp.maximum(m, s_self)
        p_self = jnp.exp(s_self - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + p_self
        # v_new: (B,1,kpr,dh) -> per-q-head row via kmap
        vn = jnp.take_along_axis(
            jnp.broadcast_to(v_new[:, 0][:, None], (B, qpr, kpr, dh)),
            jnp.broadcast_to(kmap[None, :, None, None], (B, qpr, 1, dh)),
            axis=2)[:, :, 0]
        acc = acc * corr[..., None] + p_self[..., None] * \
            vn.astype(jnp.float32)
        m = m_new
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out[:, None].astype(q.dtype)


# ---------------------------------------------------------------------------
# cache plumbing
# ---------------------------------------------------------------------------

def _gather_pool_view(kc: jax.Array, vc: jax.Array,
                      ks: Optional[jax.Array], vs: Optional[jax.Array],
                      tables: jax.Array, layer: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """The gather oracle's contiguous (B, T*bs, kpr, dh) K/V view of the
    head-major pool through ``tables`` (B, T), dequantized when the pool
    carries scale side-arrays ``ks``/``vs``.  ``layer``: the pools are
    the layers' stacks and the view is of that layer."""
    kview = gather_kv_pages(kc, tables, layer)
    vview = gather_kv_pages(vc, tables, layer)
    if ks is not None:
        kview = kview.astype(jnp.float32) * \
            gather_kv_pages(ks, tables, layer).astype(jnp.float32)[..., None]
        vview = vview.astype(jnp.float32) * \
            gather_kv_pages(vs, tables, layer).astype(jnp.float32)[..., None]
    return kview, vview


def _cache_insert_prefix(cache: jax.Array, kv: jax.Array,
                         env: AxisEnv) -> jax.Array:
    """Write the prefill K/V into cache[:, :S]."""
    if env.kv_seq_axis is None:
        return lax.dynamic_update_slice_in_dim(
            cache, kv.astype(cache.dtype), 0, axis=1)
    # seq-sharded cache: rank holds slice [r*Sloc, (r+1)*Sloc)
    r = lax.axis_index(env.kv_seq_axis)
    s_loc = cache.shape[1]
    start = r * s_loc
    sl = lax.dynamic_slice_in_dim(
        jnp.pad(kv, ((0, 0), (0, max(0, s_loc * env.kv_seq_width - kv.shape[1])),
                     (0, 0), (0, 0))),
        start, s_loc, axis=1)
    return sl.astype(cache.dtype)


def _cache_update(cache: jax.Array, kv_new: jax.Array,
                  positions: jax.Array) -> jax.Array:
    """Per-sequence scatter of the new token's K/V at its position."""
    def upd(c, n, p):
        return lax.dynamic_update_slice_in_dim(c, n.astype(c.dtype), p, axis=0)
    return jax.vmap(upd)(cache, kv_new, positions)


def _seq_sharded_update(kc, vc, k_new, v_new, positions, env: AxisEnv):
    """Scatter the new token into whichever rank owns its seq slot."""
    r = lax.axis_index(env.kv_seq_axis)
    s_loc = kc.shape[1]
    local_pos = positions - r * s_loc
    mine = (local_pos >= 0) & (local_pos < s_loc)
    safe = jnp.clip(local_pos, 0, s_loc - 1)

    def upd(c, n, p, m):
        new = lax.dynamic_update_slice_in_dim(c, n.astype(c.dtype), p, axis=0)
        return jnp.where(m, new, c)
    kc = jax.vmap(upd)(kc, k_new, safe, mine)
    vc = jax.vmap(upd)(vc, v_new, safe, mine)
    return kc, vc


def _seq_sharded_decode(q, kc, vc, k_new, v_new, positions, plan,
                        env: AxisEnv):
    """Flash-decode with the KV sequence sharded over ``env.kv_seq_axis``:
    each rank reduces its slice; partial (acc, l, m) merge via psum —
    the sequence-parallel analog of ESL's partial-product streaming."""
    a = plan.attn
    r = lax.axis_index(env.kv_seq_axis)
    s_loc = kc.shape[1]
    kmap = local_kmap(plan, env)
    # include the *new* token separately (it may belong to another rank's
    # slice; adding it once on rank 0 keeps the psum exact)
    ke = _expand_kv(kc, kmap, a.q_per_rank)
    ve = _expand_kv(vc, kmap, a.q_per_rank)
    scale = 1.0 / math.sqrt(a.d_head)
    q32 = q.astype(jnp.float32) * scale                   # (B,1,H,dh)
    kv_pos = r * s_loc + jnp.arange(s_loc)
    s = jnp.einsum("bqhd,bkhd->bhqk", q32, ke.astype(jnp.float32))
    ok = kv_pos[None, :] < positions[:, None]             # strictly past
    s = jnp.where(ok[:, None, None, :], s, big_neg(jnp.float32))
    m_loc = jnp.max(s, -1)
    # current token attends itself: fold in on every rank after global max
    kn = _expand_kv(k_new, kmap, a.q_per_rank).astype(jnp.float32)
    vn = _expand_kv(v_new, kmap, a.q_per_rank).astype(jnp.float32)
    s_self = jnp.einsum("bqhd,bkhd->bhqk", q32, kn)       # (B,H,1,1)
    m_glob = lax.pmax(jnp.maximum(m_loc, s_self[..., 0]), env.kv_seq_axis)
    p = jnp.exp(s - m_glob[..., None])
    l_loc = jnp.sum(p, -1)
    acc = jnp.einsum("bhqk,bkhd->bhqd", p, ve.astype(jnp.float32))
    l = lax.psum(l_loc, env.kv_seq_axis)
    acc = lax.psum(acc, env.kv_seq_axis)
    p_self = jnp.exp(s_self[..., 0] - m_glob)
    l = l + p_self
    acc = acc + p_self[..., None] * vn.transpose(0, 2, 1, 3)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def init_cache(plan, batch: int, max_seq: int, dtype=jnp.bfloat16,
               abstract: bool = False, kv_seq_width: int = 1,
               paged: bool = False, num_blocks: int = 0,
               block_size: int = 0, scale_dtype=None):
    """Per-layer KV cache in the stored (local-head) layout.

    Dense: global logical shape (B, max_seq, Gp, dh); under kv-seq
    sharding the stored seq dim is max_seq/width per rank (global held
    as rank-major).

    Paged (``paged=True``): a shared pool (num_blocks, Gp, block_size,
    dh) with **no batch dimension** — requests own disjoint block sets
    via block tables (block 0 reserved as the null block).  Memory
    scales with resident tokens, not slots x worst-case length.  The
    pool is head-major: each (block, head) is one contiguous
    (block_size, dh) plane, the tile the paged kernel streams (a
    seq-major (block_size, Gp, dh) block would hand Mosaic a (1, dh)
    slice of a (Gp, dh) plane, which it refuses for Gp > 1).

    ``scale_dtype`` (paged only) marks the pool as quantized: ``dtype``
    is the int8/fp8 storage type and two scale side-arrays
    ``k_scale``/``v_scale`` of shape (num_blocks, Gp, block_size) ride
    alongside the values — one absmax scale per stored row per head,
    zero-initialized so the null block dequantizes to exact zeros.
    """
    a = plan.attn
    gp = a.gp
    if paged:
        assert kv_seq_width == 1, "paged cache is single-rank (no kv-seq)"
        assert num_blocks >= 2 and block_size > 0, (num_blocks, block_size)
        shape = (num_blocks, gp, block_size, a.d_head)
    else:
        assert scale_dtype is None, \
            "quantized KV storage needs the paged pool (row scatters " \
            "carry the scales; the dense cache has no side arrays)"
        s = max_seq // kv_seq_width
        shape = (batch, max_seq, gp, a.d_head) if kv_seq_width == 1 else \
            (batch, kv_seq_width, s, gp, a.d_head)

    def leaf(shp, dt):
        return (jax.ShapeDtypeStruct(shp, dt) if abstract
                else jnp.zeros(shp, dt))

    out = {"k": leaf(shape, dtype), "v": leaf(shape, dtype)}
    if scale_dtype is not None:
        out["k_scale"] = leaf(shape[:-1], scale_dtype)
        out["v_scale"] = leaf(shape[:-1], scale_dtype)
    return out
