"""Streamlined decode engine (C1) — kernel-backed generation step.

The LPU's thesis: generation-stage latency == weight-stream time, so the
decode path must be a chain of bandwidth-saturating streamed ops with
zero reshaping between them.  This module is that chain on TPU:

    gemv(QKV, fused)  ->  decode_attention (fused flash, SXE||VXE)
 -> gemv(O) -> gemv(FC1 gate|up, fused) -> gemv(FC2)

Every matmul is the Pallas GEMV (``kernels/gemv``) whose BlockSpecs
realize the ``I x v x 2B x freq = BW`` balance; attention is the fused
``kernels/decode_attention``.  ``use_kernels=False`` routes to the jnp
oracles — bit-compatible (tests/test_streamline.py), used by the
dry-run so XLA's fusion stands in for the hand kernels on CPU.

This is the single-device inner loop; the ESL ring (core/esl.py) wraps
it for tensor parallelism (the kernels consume rank-local tiles).  The
KV side accepts either the dense per-slot cache or the serving engine's
shared block pool (``block_table``) — same streamed chain, the table
only redirects where KV tiles live (tests/test_streamline.py proves
dense/paged parity).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.ops import (decode_attention,
                                                paged_decode_attention,
                                                resolve_paged_kernel)
from repro.kernels.decode_attention.ref import gather_kv_pages
from repro.kernels.gemv.ops import gemv, quantize_weight
from repro.models.common import apply_norm, apply_rope

Params = Dict[str, jax.Array]

W_DTYPES = ("auto", "int8")


def _mm(x2d: jax.Array, w: jax.Array, b: Optional[jax.Array], *,
        use_kernels: bool, interpret: Optional[bool] = None,
        quantize: bool = False) -> jax.Array:
    if quantize:
        # int8 weight stream: per-output-column absmax quantization, the
        # scale applied once at the kernel's f32 flush — halves the HBM
        # bytes of the dominant weight stream (C1's balance knob)
        qw, ws = quantize_weight(w)
        return gemv(x2d, qw, b, w_scale=ws, use_pallas=use_kernels,
                    interpret=interpret)
    return gemv(x2d, w, b, use_pallas=use_kernels, interpret=interpret)


def decode_layer(p: Params, x: jax.Array, cache: Dict[str, jax.Array],
                 positions: jax.Array, *, cfg, plan,
                 use_kernels: bool = True, interpret: Optional[bool] = None,
                 block_table: Optional[jax.Array] = None,
                 paged_kernel: str = "auto",
                 w_dtype: str = "auto"
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decoder layer, one token, single device (tp folded outside).

    x: (B, D); cache: {'k','v': (B, S, G, dh)}; positions: (B,).
    Returns (y (B, D), new cache).  Weights in the mapper's stored layout.

    Paged mode (``block_table`` (B, T) given): cache k/v are the shared
    block pool (N, G, bs, dh).  The new token's KV scatters into
    physical block ``table[b, pos // bs]`` at offset ``pos % bs`` — the
    serving engine's pool layout, tp-folded just like the weights (each
    rank holds its head shard of every block).  ``paged_kernel``:
    ``"stream"`` keeps the chain gather-free — the paged kernel consumes
    KV tiles straight from the updated pool through the block table;
    ``"gather"`` materializes the per-request contiguous view first (the
    reference oracle); ``"auto"`` streams when the stored GQA layout is
    block-regular.

    Quantized pool (``k_scale``/``v_scale`` leaves present): the new
    token's KV rows quantize at scatter time and the kernel dequantizes
    in its tile loop — the decode chain reads the pool post-update, so
    the current token is attended via its quantized round-trip (same
    stored-value contract as the full model path).  ``w_dtype="int8"``
    streams every gemv's weights int8 with per-output-column scales.
    """
    if w_dtype not in W_DTYPES:
        raise ValueError(f"w_dtype={w_dtype!r} not in {W_DTYPES}")
    qw = w_dtype == "int8"
    a = plan.attn
    B, D = x.shape
    qpr, kpr, dh = a.q_per_rank, a.kv_per_rank, a.d_head

    h = apply_norm(p["ln1"], x, cfg.norm)
    wq = p["attn"]["wq"].reshape(D, qpr * dh)
    wk = p["attn"]["wk"].reshape(D, kpr * dh)
    wv = p["attn"]["wv"].reshape(D, kpr * dh)
    wqkv = jnp.concatenate([wq, wk, wv], -1)     # ONE weight stream (C1)
    bqkv = None
    if "bq" in p["attn"]:
        bqkv = jnp.concatenate([p["attn"][k].reshape(-1)
                                for k in ("bq", "bk", "bv")])
    qkv = _mm(h, wqkv, bqkv, use_kernels=use_kernels, interpret=interpret,
              quantize=qw)
    q, k_new, v_new = jnp.split(qkv, [qpr * dh, (qpr + kpr) * dh], -1)
    q = q.reshape(B, qpr, dh)
    k_new = k_new.reshape(B, kpr, dh)
    v_new = v_new.reshape(B, kpr, dh)
    if cfg.positional == "rope":
        q = apply_rope(q[:, None], positions[:, None],
                       cfg.rope_theta)[:, 0]
        k_new = apply_rope(k_new[:, None], positions[:, None],
                           cfg.rope_theta)[:, 0]

    quantized = block_table is not None and "k_scale" in cache
    ks = vs = None
    if block_table is not None:
        # pool scatter: one (G, dh) row per sequence; inactive slots all
        # target the null block 0 (don't-care, masked by valid length)
        bs_blk = cache["k"].shape[2]
        blk = jnp.take_along_axis(block_table,
                                  (positions // bs_blk)[:, None],
                                  axis=1)[:, 0]
        off = positions % bs_blk
        if quantized:
            from repro.serving.kv_cache import quantize_kv_rows
            kq, ksc = quantize_kv_rows(k_new, cache["k"].dtype,
                                       cache["k_scale"].dtype)
            vq, vsc = quantize_kv_rows(v_new, cache["v"].dtype,
                                       cache["v_scale"].dtype)
            kc = cache["k"].at[blk, :, off].set(kq)
            vc = cache["v"].at[blk, :, off].set(vq)
            ks = cache["k_scale"].at[blk, :, off].set(ksc)
            vs = cache["v_scale"].at[blk, :, off].set(vsc)
        else:
            kc = cache["k"].at[blk, :, off].set(
                k_new.astype(cache["k"].dtype))
            vc = cache["v"].at[blk, :, off].set(
                v_new.astype(cache["v"].dtype))
        mode = resolve_paged_kernel(plan, paged_kernel)
        if mode == "stream":
            # gather-free: the kernel's scalar-prefetched table resolves
            # each KV tile's pool address — the streamed chain never
            # materializes a per-request contiguous copy
            attn = paged_decode_attention(
                q, kc, vc, block_table, positions + 1,
                k_scale=ks, v_scale=vs,
                use_pallas=use_kernels, interpret=interpret)
            attn_done = True
        else:
            k_view = gather_kv_pages(kc, block_table)
            v_view = gather_kv_pages(vc, block_table)
            if quantized:
                k_view = k_view.astype(jnp.float32) * gather_kv_pages(
                    ks, block_table)[..., None]
                v_view = v_view.astype(jnp.float32) * gather_kv_pages(
                    vs, block_table)[..., None]
            attn_done = False
    else:
        def upd(c, n, pos):
            return jax.lax.dynamic_update_slice_in_dim(
                c, n[None].astype(c.dtype), pos, axis=0)
        kc = jax.vmap(upd)(cache["k"], k_new, positions)
        vc = jax.vmap(upd)(cache["v"], v_new, positions)
        k_view, v_view = kc, vc
        attn_done = False

    if not attn_done:
        attn = decode_attention(q, k_view, v_view, positions + 1,
                                use_pallas=use_kernels, interpret=interpret)
    wo = p["attn"]["wo"].reshape(qpr * dh, D)
    x = x + _mm(attn.reshape(B, -1), wo, None, use_kernels=use_kernels,
                interpret=interpret, quantize=qw)

    h = apply_norm(p["ln2"], x, cfg.norm)
    if "wg" in p["mlp"]:
        w1 = jnp.concatenate([p["mlp"]["wg"], p["mlp"]["wu"]], -1)
        gu = _mm(h, w1, None, use_kernels=use_kernels, interpret=interpret,
                 quantize=qw)
        g, u = jnp.split(gu, 2, -1)
        act = jax.nn.silu(g) * u if cfg.activation == "silu" else \
            jax.nn.gelu(g) * u
    else:
        act = _mm(h, p["mlp"]["wi"], p["mlp"].get("bi"),
                  use_kernels=use_kernels, interpret=interpret, quantize=qw)
        act = jax.nn.relu(act) if cfg.activation == "relu" else \
            jax.nn.gelu(act)
    y = _mm(act, p["mlp"]["wd"], p["mlp"].get("bd"),
            use_kernels=use_kernels, interpret=interpret, quantize=qw)
    new_cache = {"k": kc, "v": vc}
    if quantized:
        new_cache["k_scale"], new_cache["v_scale"] = ks, vs
    return x + y, new_cache


def chunk_prefill_layer(p: Params, x: jax.Array,
                        cache: Dict[str, jax.Array],
                        block_table: jax.Array, start: jax.Array,
                        n_valid: jax.Array, *, cfg, plan,
                        use_kernels: bool = True, interpret: Optional[bool] = None,
                        paged_kernel: str = "auto"
                        ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decoder layer over ONE prefill chunk, single device.

    The prefill-with-initial-carry entry of the streamed chain: a chunk
    of C prompt tokens runs through the SAME streamed ops as decode —
    the fused QKV / O / FC gemvs simply carry C rows instead of one,
    and attention REUSES the paged decode kernel's online-softmax fold
    by treating the chunk as a batch of C single-token queries with
    per-query valid lengths ``start + i + 1`` over the request's
    (broadcast) block table.  Causality over history + the chunk's own
    causal prefix falls out of the kernel's length masking; nothing new
    is lowered for prefill.

    x: (C, D) chunk activations; cache: {'k','v': (N, G, bs, dh)} the
    shared pool; block_table: (T,); start: absolute offset of the chunk;
    n_valid: valid rows (padded tail rows land in the null block 0).
    Returns (y (C, D), updated pool).
    """
    a = plan.attn
    C, D = x.shape
    qpr, kpr, dh = a.q_per_rank, a.kv_per_rank, a.d_head

    h = apply_norm(p["ln1"], x, cfg.norm)
    wq = p["attn"]["wq"].reshape(D, qpr * dh)
    wk = p["attn"]["wk"].reshape(D, kpr * dh)
    wv = p["attn"]["wv"].reshape(D, kpr * dh)
    wqkv = jnp.concatenate([wq, wk, wv], -1)
    bqkv = None
    if "bq" in p["attn"]:
        bqkv = jnp.concatenate([p["attn"][k].reshape(-1)
                                for k in ("bq", "bk", "bv")])
    qkv = _mm(h, wqkv, bqkv, use_kernels=use_kernels, interpret=interpret)
    q, k_new, v_new = jnp.split(qkv, [qpr * dh, (qpr + kpr) * dh], -1)
    q = q.reshape(C, qpr, dh)
    k_new = k_new.reshape(C, kpr, dh)
    v_new = v_new.reshape(C, kpr, dh)
    positions = start + jnp.arange(C, dtype=jnp.int32)
    if cfg.positional == "rope":
        q = apply_rope(q[None], positions[None], cfg.rope_theta)[0]
        k_new = apply_rope(k_new[None], positions[None], cfg.rope_theta)[0]

    from repro.serving.kv_cache import scatter_chunk_rows
    valid = positions < start + n_valid
    kc = scatter_chunk_rows(cache["k"], k_new, block_table, positions,
                            valid)
    vc = scatter_chunk_rows(cache["v"], v_new, block_table, positions,
                            valid)
    lens = jnp.minimum(positions + 1, start + n_valid)
    mode = resolve_paged_kernel(plan, paged_kernel)
    if mode == "stream":
        tabs = jnp.broadcast_to(block_table[None],
                                (C, block_table.shape[0]))
        attn = paged_decode_attention(q, kc, vc, tabs, lens,
                                      use_pallas=use_kernels,
                                      interpret=interpret)
    else:
        k_view = gather_kv_pages(kc, block_table[None])
        v_view = gather_kv_pages(vc, block_table[None])
        k_view = jnp.broadcast_to(k_view, (C,) + k_view.shape[1:])
        v_view = jnp.broadcast_to(v_view, (C,) + v_view.shape[1:])
        attn = decode_attention(q, k_view, v_view, lens,
                                use_pallas=use_kernels,
                                interpret=interpret)
    wo = p["attn"]["wo"].reshape(qpr * dh, D)
    x = x + _mm(attn.reshape(C, -1), wo, None, use_kernels=use_kernels,
                interpret=interpret)

    h = apply_norm(p["ln2"], x, cfg.norm)
    if "wg" in p["mlp"]:
        w1 = jnp.concatenate([p["mlp"]["wg"], p["mlp"]["wu"]], -1)
        gu = _mm(h, w1, None, use_kernels=use_kernels, interpret=interpret)
        g, u = jnp.split(gu, 2, -1)
        act = jax.nn.silu(g) * u if cfg.activation == "silu" else \
            jax.nn.gelu(g) * u
    else:
        act = _mm(h, p["mlp"]["wi"], p["mlp"].get("bi"),
                  use_kernels=use_kernels, interpret=interpret)
        act = jax.nn.relu(act) if cfg.activation == "relu" else \
            jax.nn.gelu(act)
    y = _mm(act, p["mlp"]["wd"], p["mlp"].get("bd"),
            use_kernels=use_kernels, interpret=interpret)
    return x + y, {"k": kc, "v": vc}


def verify_layer(p: Params, x: jax.Array, cache: Dict[str, jax.Array],
                 block_tables: jax.Array, positions: jax.Array, *, cfg,
                 plan, use_kernels: bool = True, interpret: Optional[bool] = None,
                 paged_kernel: str = "auto", w_dtype: str = "auto"
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decoder layer over one speculative verify window.

    A verify window flattens every slot's (last token + k drafts) into
    Q = B*(k+1) single-token queries, each with its OWN block table and
    absolute position.  That is EXACTLY the streamed decode dataflow:
    :func:`decode_layer` scatters all Q new KV rows into the pool first
    and then attends each query over ``positions + 1`` tokens through
    its table — so draft i sees drafts < i of the same window (their
    positions are smaller) plus all resident history, with zero new
    kernel code.  This delegate exists to name that contract; the
    full-model analogue is :func:`repro.models.attention.verify_attention`.

    x: (Q, D); block_tables: (Q, T); positions: (Q,).
    """
    return decode_layer(p, x, cache, positions, cfg=cfg, plan=plan,
                        use_kernels=use_kernels, interpret=interpret,
                        block_table=block_tables,
                        paged_kernel=paged_kernel, w_dtype=w_dtype)


def stream_bytes_per_layer(cfg, plan, kv_len: int) -> int:
    """Analytic bytes streamed per token per layer (latency model input)."""
    a = plan.attn
    d = cfg.d_model
    wbytes = 2 * (d * (a.hp + 2 * a.gp) * a.d_head // plan.tp
                  + a.hp * a.d_head * d // plan.tp)
    n_mat = 3 if cfg.mlp_gated else 2
    wbytes += 2 * n_mat * d * plan.d_ff_padded // plan.tp
    kv_bytes = 2 * 2 * kv_len * (a.gp // plan.tp) * a.d_head
    return wbytes + kv_bytes
