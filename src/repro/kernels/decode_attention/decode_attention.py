"""Fused decode attention — the SXE∥VXE dual-path timeline (Fig. 3b).

One generated token attends a streamed KV cache.  The paper's dataflow:
the Key tile streams into the matmul path (SXE) producing a Score, the
softmax (VXE) of tile *i* runs while tile *i+1*'s dot executes, and the
Value product accumulates output-stationary.  On TPU the same overlap
falls out of a single fused kernel: MXU dots and VPU exp/max/sum issue
concurrently per KV tile with an online-softmax carry in VMEM scratch.

Grid: (B, G, S_tiles) — S minor, so the carry (m, l, acc) lives in
scratch across the KV stream.  GQA: all ``gs`` query heads of a KV head
are processed together, so each KV tile is read exactly once per group —
and the cache layout is already (seq-major, head-minor), the mapper's
"natural transpose": no transpose op ever materializes.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

LANE = 128


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, s_tiles: int, block_s: int,
                   scale: float):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale            # (gs, dh)
    k = k_ref[0, :, 0].astype(jnp.float32)              # (block_s, dh)
    v = v_ref[0, :, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (gs,S_blk)
    length = len_ref[0]
    pos = t * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < length, s, -1e30)

    m_prev = m_ref[...]                                  # (gs, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (gs, dh)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new

    @pl.when(t == s_tiles - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _head_row(scale_ref, g) -> jax.Array:
    """Row ``g`` of a (G, bs) scale tile as a (1, bs) f32 vector.

    Selected by a masked sum rather than a dynamic sublane slice: Mosaic
    cannot prove a dynamic row of a packed 16-bit tile aligned."""
    sc = scale_ref[...].astype(jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
    return jnp.sum(jnp.where(rows == g, sc, 0.0), axis=0, keepdims=True)


def _paged_decode_kernel(*refs, n_prefetch: int, t_blocks: int,
                         block_s: int, scale: float,
                         quantized: bool = False, planes_t: bool = False):
    """Paged variant: same online-softmax stream as ``_decode_kernel`` but
    KV tiles are fetched through the block table (scalar-prefetched, so the
    DMA address is known before the body runs — the LPU's address-generator
    indirection).  Tile ``t`` covers logical positions [t*bs, (t+1)*bs).

    With the optional ``kn_ref/vn_ref`` inputs (decode streaming: the cache
    is read *pre-update*), the just-generated token's K/V is folded into
    the online-softmax carry after the last pool tile — the model path's
    read-then-scatter contract, so the pool is never copied to append one
    row.

    ``quantized``: the pool tiles are int8/fp8 and ``ks_ref/vs_ref`` hold
    the (G, bs) absmax scales of the tile's block; row ``g`` is this kv
    head's.  Dequantization happens HERE, inside the tile loop, so fp KV
    values never round-trip through HBM.  A row scale is constant along
    ``dh``, so it multiplies the score column (K) and the probability
    column (V) instead of the tile — no (bs, 1) relayout.  The folded new
    token's K/V stays full precision (it is a fresh activation, not pool
    storage).

    ``n_prefetch`` scalar-prefetch refs lead: (lengths, tables), or
    (layer, lengths, tables) for a stacked pool.  ``planes_t``: the K/V
    tiles arrive as (dh, bs) planes (bs on the lanes) instead of
    (bs, dh); the two dots contract over the matching axis."""
    len_ref, tbl_ref = refs[n_prefetch - 2:n_prefetch]
    q_ref, k_ref, v_ref, *rest = refs[n_prefetch:]
    if quantized:
        ks_ref, vs_ref = rest[0], rest[1]
        rest = rest[2:]
    fold_new = len(rest) == 6
    if fold_new:
        kn_ref, vn_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    g = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale            # (gs, dh)
    k = k_ref[...].astype(jnp.float32)          # (block_s, dh) | (dh, bs)
    v = v_ref[...].astype(jnp.float32)
    kv_axis = 0 if planes_t else 1              # k's dh axis
    s = jax.lax.dot_general(q, k, (((1,), (kv_axis,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if quantized:
        s = s * _head_row(ks_ref, g)
    length = len_ref[b]
    pos = t * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < length, s, -1e30)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
    if quantized:
        p = p * _head_row(vs_ref, g)
    pv = jax.lax.dot_general(p, v, (((1,), (1 - kv_axis,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = m_new

    @pl.when(t == t_blocks - 1)
    def _flush():
        if fold_new:
            kn = kn_ref[0].astype(jnp.float32)          # (1, dh)
            vn = vn_ref[0].astype(jnp.float32)
            s_self = jax.lax.dot_general(
                q, kn, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)     # (gs, 1)
            m_p = m_ref[...]
            m_f = jnp.maximum(m_p, s_self)
            p_self = jnp.exp(s_self - m_f)
            c = jnp.exp(m_p - m_f)
            l_ref[...] = l_ref[...] * c + p_self
            acc_ref[...] = acc_ref[...] * c + p_self * vn
            m_ref[...] = m_f
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def lane_dense_planes(bs: int, dh: int) -> bool:
    """Whether a (bs, dh) pool plane is stored with ``bs`` on the lanes.

    The TPU lays a row-major (..., bs, dh) array out with its last two
    dims swapped when ``dh`` would leave lanes empty and ``bs`` fills
    them (d_head 64 at block 128): the pool then lives as (dh, bs)
    planes.  The stacked-pool kernel reads those planes as they lie, so
    nothing converts the pool to the row-major tile."""
    return dh % LANE != 0 and bs % LANE == 0


def paged_decode_attention_pallas(q: jax.Array, k_pages: jax.Array,
                                  v_pages: jax.Array,
                                  block_tables: jax.Array,
                                  lengths: jax.Array, *,
                                  layer: jax.Array = None,
                                  k_new: jax.Array = None,
                                  v_new: jax.Array = None,
                                  k_scale: jax.Array = None,
                                  v_scale: jax.Array = None,
                                  interpret: Optional[bool] = None
                                  ) -> jax.Array:
    """q: (B,H,dh); k_pages,v_pages: (N,G,bs,dh) head-major shared pool
    with H = G*gs; block_tables: (B,T) physical block per logical block;
    lengths: (B,).  Returns (B,H,dh).  The block table rides scalar
    prefetch so each KV tile's pool address is resolved before its DMA
    issues.

    Head-major is what makes the kernel compile: a KV tile is the whole
    trailing (bs, dh) plane of one (block, head), so Mosaic's rule that a
    block's last two dims be (8, 128)-aligned or span the array holds for
    any ``bs`` and ``d_head`` (64-wide heads included).

    Stacked pool (dispatch by rank): k_pages,v_pages (L,N,G,bs,dh) hold
    every layer, and ``layer`` (a traced int32 scalar) picks one.  The
    layer index rides scalar prefetch beside the table, so the KV index
    map is (layer, tbl[b, t], g, 0, 0) and the layer's slice is never
    cut out of the pool: the decode scan hands its carry straight in.
    Where :func:`lane_dense_planes` holds, the kernel reads the (dh, bs)
    planes the pool is stored as (a transposed view XLA lays over the
    same bytes).  A 4-D pool keeps the (bs, dh) per-layer call.

    ``k_new/v_new`` ((B,G,dh), both or neither): the current token's K/V,
    folded into the softmax carry *after* the streamed pool tiles — used
    by the decode path that reads the cache pre-update and lets the
    caller scatter the new row into the pool afterwards.

    ``k_scale/v_scale`` ((N,G,bs), stacked (L,N,G,bs); both or neither):
    the quantized pool's absmax scale side-arrays; the whole (G, bs)
    scale plane of a block rides the SAME block-table indirection as the
    value tiles and dequantization runs inside the tile loop."""
    B, H, dh = q.shape
    stacked = k_pages.ndim == 5
    assert stacked == (layer is not None), (k_pages.shape, layer)
    N, G, bs, _ = k_pages.shape[-4:]
    T = block_tables.shape[1]
    assert H % G == 0, (H, G)
    assert (k_new is None) == (v_new is None)
    assert (k_scale is None) == (v_scale is None)
    gs = H // G
    qg = q.reshape(B * G, gs, dh)
    planes_t = stacked and lane_dense_planes(bs, dh)
    prefetch = [lengths, block_tables]
    if stacked:
        prefetch.insert(0, jnp.reshape(layer, (1,)).astype(jnp.int32))

    def lead(pf):
        """Leading block index of a pool operand: the stacked pool's
        layer (scalar-prefetch ref 0), none for a 4-D pool."""
        return (pf[0][0],) if stacked else ()
    n_lead = int(stacked)

    kernel = functools.partial(_paged_decode_kernel,
                               n_prefetch=len(prefetch), t_blocks=T,
                               block_s=bs, scale=1.0 / math.sqrt(dh),
                               quantized=k_scale is not None,
                               planes_t=planes_t)
    if planes_t:
        k_pages = jnp.swapaxes(k_pages, -1, -2)
        v_pages = jnp.swapaxes(v_pages, -1, -2)
    plane = (dh, bs) if planes_t else (bs, dh)
    kv_spec = pl.BlockSpec(
        (None,) * (n_lead + 2) + plane,
        lambda b, g, t, *pf: lead(pf) + (pf[-1][b, t], g, 0, 0))
    in_specs = [
        pl.BlockSpec((1, gs, dh),
                     lambda b, g, t, *pf: (b * G + g, 0, 0)),
        kv_spec, kv_spec,
    ]
    operands = [qg, k_pages, v_pages]
    if k_scale is not None:
        scale_spec = pl.BlockSpec(
            (None,) * (n_lead + 1) + (G, bs),
            lambda b, g, t, *pf: lead(pf) + (pf[-1][b, t], 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    if k_new is not None:
        new_spec = pl.BlockSpec((1, 1, dh),
                                lambda b, g, t, *pf: (b * G + g, 0, 0))
        in_specs += [new_spec, new_spec]
        operands += [k_new.reshape(B * G, 1, dh),
                     v_new.reshape(B * G, 1, dh)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, G, T),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, gs, dh),
                               lambda b, g, t, *pf: (b * G + g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((gs, 1), jnp.float32),
            pltpu.VMEM((gs, 1), jnp.float32),
            pltpu.VMEM((gs, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * G, gs, dh), q.dtype),
        interpret=interpret_mode(interpret),
        name="paged_decode_attention",
    )(*prefetch, *operands)
    return out.reshape(B, H, dh)


def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                            lengths: jax.Array, *, block_s: int = 512,
                            interpret: Optional[bool] = None) -> jax.Array:
    """q: (B,H,dh); k,v: (B,S,G,dh) with H = G*gs (block-regular GQA);
    lengths: (B,) valid cache length.  Returns (B,H,dh)."""
    B, H, dh = q.shape
    _, S, G, _ = k.shape
    assert H % G == 0, (H, G)
    gs = H // G
    block_s = min(block_s, S)
    assert S % block_s == 0, (S, block_s)
    s_tiles = S // block_s
    qg = q.reshape(B * G, gs, dh)

    kernel = functools.partial(_decode_kernel, s_tiles=s_tiles,
                               block_s=block_s,
                               scale=1.0 / math.sqrt(dh))
    out = pl.pallas_call(
        kernel,
        grid=(B, G, s_tiles),
        in_specs=[
            pl.BlockSpec((1,), lambda b, g, t: (b,)),
            pl.BlockSpec((1, gs, dh), lambda b, g, t: (b * G + g, 0, 0)),
            pl.BlockSpec((1, block_s, 1, dh), lambda b, g, t: (b, t, g, 0)),
            pl.BlockSpec((1, block_s, 1, dh), lambda b, g, t: (b, t, g, 0)),
        ],
        out_specs=pl.BlockSpec((1, gs, dh), lambda b, g, t: (b * G + g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * G, gs, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((gs, 1), jnp.float32),
            pltpu.VMEM((gs, 1), jnp.float32),
            pltpu.VMEM((gs, dh), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
        name="decode_attention",
    )(lengths, qg, k, v)
    return out.reshape(B, H, dh)
