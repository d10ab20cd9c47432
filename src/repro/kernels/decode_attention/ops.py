"""jit'd wrapper for fused decode attention with KV-tile planning.

``block_s`` sizing: the KV stream tile is (block_s, dh) per K and V; the
kernel is bandwidth-bound (intensity ~ 1 flop/byte), so like the GEMV we
choose the largest 128-aligned tile fitting the double-buffered VMEM
budget — keeping the cache stream saturated is the whole game.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.decode_attention.decode_attention import (
    LANE, decode_attention_pallas, paged_decode_attention_pallas)
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                paged_decode_attention_ref)

VMEM_BUDGET = 32 * 2 ** 20


def paged_stream_supported(plan) -> bool:
    """True when paged decode can stream through the Pallas kernel.

    The one condition is the plan's stored GQA layout: it must be
    block-regular (the kernel maps q head ``h`` to kv head ``h // gs``
    with no per-head gather).  Tile alignment is not a condition: the
    head-major pool hands the kernel whole (bs, dh) planes, which Mosaic
    accepts for any block size and head width."""
    a = plan.attn
    return a is not None and a.block_regular


def resolve_paged_kernel(plan, requested: str) -> str:
    """Resolve a ``paged_kernel`` request to the dataflow that will run.

    ``"auto"`` becomes ``"stream"`` when :func:`paged_stream_supported`
    allows it, else ``"gather"``; an explicit ``"stream"`` on an
    ineligible plan raises instead of silently degrading.  Every
    dispatch site (model decode, streamline decode_layer, the serving
    engine) resolves through this one function so they can never
    disagree."""
    if requested not in ("auto", "stream", "gather"):
        raise ValueError(f"paged_kernel={requested!r} not in "
                         "('auto', 'stream', 'gather')")
    ok = paged_stream_supported(plan)
    if requested == "auto":
        return "stream" if ok else "gather"
    if requested == "stream" and not ok:
        raise ValueError(
            "paged_kernel='stream' needs a block-regular stored GQA "
            f"layout; plan for {plan.arch} cannot stream (use 'gather' "
            "or 'auto')")
    return requested


def plan_block_s(S: int, dh: int, gs: int, dtype_bytes: int = 2,
                 override: int = 0) -> int:
    """Pick the KV stream tile: largest 128-aligned divisor of ``S``
    whose double-buffered K+V footprint fits the VMEM budget.

    ``override`` (the ``--block-s`` knob) short-circuits the search so
    real-hardware runs can sweep tile sizes against this planner — the
    ROADMAP's tune-on-TPU item.  It is clamped to ``S`` and must divide
    it (the kernels' grids assume exact tiling).
    """
    if override:
        bs = min(override, S)
        if S % bs:
            raise ValueError(
                f"block_s override {override} does not tile S={S}")
        if bs % LANE and bs != S:
            # the compiled kernel's KV tiles must be LANE-aligned (a
            # full-span tile is exempt: the kernel clamps to S) — reject
            # here so a TPU sweep fails at plan time, not Mosaic lowering
            raise ValueError(
                f"block_s override {override} is not LANE({LANE})-"
                f"aligned (or the full span {S})")
        return bs
    bs = min(S, 4096)
    while bs > LANE:
        tile = 2 * bs * dh * dtype_bytes * 2     # K+V, double-buffered
        if S % bs == 0 and tile <= VMEM_BUDGET:
            return bs
        bs //= 2
    return max(LANE, bs)


@partial(jax.jit, static_argnames=("use_pallas", "interpret", "block_s"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, *, use_pallas: bool = True,
                     interpret: Optional[bool] = None,
                     block_s: int = 0) -> jax.Array:
    """q: (B,H,dh); k,v: (B,S,G,dh); lengths: (B,) -> (B,H,dh).

    ``use_pallas=False`` asks for the oracle by name.  ``block_s``
    overrides the planned KV stream tile (0 = let :func:`plan_block_s`
    choose).  Shapes the kernel cannot take raise.
    """
    B, H, dh = q.shape
    S, G = k.shape[1], k.shape[2]
    if not use_pallas:
        gs = max(H // G, 1)
        ke = jnp.repeat(k, gs, axis=2)[:, :, :H]
        ve = jnp.repeat(v, gs, axis=2)[:, :, :H]
        return decode_attention_ref(q, ke, ve, lengths)
    if H % G:
        raise ValueError(f"decode_attention: {H} q heads do not group "
                         f"over {G} kv heads")
    if not interpret_mode(interpret) and G > 1:
        # the dense (B, S, G, dh) cache hands the kernel a (block_s, 1)
        # slice of its (G, dh) trailing plane, which Mosaic refuses
        raise ValueError("decode_attention: the compiled dense kernel "
                         f"takes one kv head per cache (got G={G})")
    bs = min(plan_block_s(S, dh, H // G, k.dtype.itemsize,
                          override=block_s), S)
    if S % bs:
        raise ValueError(f"decode_attention: no KV tile divides S={S}")
    return decode_attention_pallas(q, k, v, lengths, block_s=bs,
                                   interpret=interpret)


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, *,
                           layer: Optional[jax.Array] = None,
                           k_new: Optional[jax.Array] = None,
                           v_new: Optional[jax.Array] = None,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None,
                           use_pallas: bool = True,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Paged decode attention over a shared block pool.

    q: (B,H,dh); k_pages,v_pages: (N,G,bs,dh) head-major; block_tables:
    (B,T); lengths: (B,) -> (B,H,dh).  The Pallas kernel streams KV tiles
    straight from the pool through the block-table indirection (no
    contiguous copy).  ``use_pallas=False`` asks for the gather oracle
    (:func:`paged_decode_attention_ref`) by name; nothing falls back to it.

    A 5-D pool (L,N,G,bs,dh) is every layer's pool stacked, and
    ``layer`` (int32 scalar, traced) is the one attended: the kernel
    takes the layer index in scalar prefetch and reads the stack in
    place, the oracle indexes it.  The rank of the pool picks the call;
    a 4-D pool takes no ``layer``.

    ``k_new/v_new`` ((B,G,dh)): the current token's K/V, attended *in
    addition to* the ``lengths`` resident positions — the pool is read
    pre-update and the caller scatters the new row afterwards, so decode
    never rewrites (or copies) the pool to append one token.

    ``k_scale/v_scale`` ((N,G,bs), stacked (L,N,G,bs)): absmax scale
    side-arrays of a quantized (int8/fp8) pool.  The kernel dequantizes
    inside its tile loop; the oracle dequantizes the gathered view —
    both read the pool at the quantized byte width.
    """
    if (k_pages.ndim == 5) != (layer is not None):
        raise ValueError("paged_decode_attention: a stacked (L,N,G,bs,dh) "
                         "pool takes a layer index, a (N,G,bs,dh) pool none "
                         f"(pool {k_pages.shape}, layer={layer})")
    if not use_pallas:
        return paged_decode_attention_ref(
            q, k_pages, v_pages, block_tables, lengths, layer=layer,
            k_new=k_new, v_new=v_new, k_scale=k_scale, v_scale=v_scale)
    H, G = q.shape[1], k_pages.shape[-3]
    if H % G:
        raise ValueError(f"paged_decode_attention: {H} q heads do not "
                         f"group over {G} kv heads")
    return paged_decode_attention_pallas(q, k_pages, v_pages, block_tables,
                                         lengths, layer=layer, k_new=k_new,
                                         v_new=v_new, k_scale=k_scale,
                                         v_scale=v_scale,
                                         interpret=interpret)
