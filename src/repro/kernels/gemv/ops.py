"""jit'd wrapper + tile planner for the decode GEMV kernel.

``plan_blocks`` realizes the LPU balance condition on TPU: pick the
largest (K_blk, N_blk) weight window that (a) fits the VMEM budget with
double-buffering and (b) keeps both dims 128-aligned so the MXU runs at
full tile occupancy.  The weight stream then saturates HBM — arithmetic
intensity of GEMV is ~1 flop/byte, far below the ridge, so bandwidth is
the roofline and the only job of the BlockSpec is to never stall the
stream.

Int8 weight streaming: ``quantize_weight`` folds a weight matrix to int8
with one absmax scale per output column, ``gemv(..., w_scale=...)``
streams the int8 tiles and applies the scale once per output tile at the
f32 flush — the weight stream (the decode roofline term) halves while
the activation stays fp, which is why ``plan_blocks`` sizes the two
operands from their OWN itemsizes.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.gemv.gemv import gemv_pallas
from repro.kernels.gemv.ref import gemv_ref

VMEM_BYTES = 64 * 2 ** 20          # ~64 MiB/core budget (v5e: 128 MiB/chip)
LANE = 128


def plan_blocks(B: int, K: int, N: int, w_bytes: int = 2,
                x_bytes: int = 0,
                vmem_budget: int = VMEM_BYTES // 2) -> Tuple[int, int]:
    """Largest aligned (block_k, block_n) with 2x buffering in budget.

    The double-buffered weight stream and the stationary activation tile
    are sized from their OWN itemsizes (``x_bytes`` defaults to
    ``w_bytes`` for uniform-precision callers): with int8 weights and
    fp16/fp32 activations a shared byte width either starves the window
    (activation width applied to the stream) or overflows VMEM (weight
    width applied to the activation).
    """
    x_bytes = x_bytes or w_bytes

    def fits(bk, bn):
        w_tile = bk * bn * w_bytes * 2              # double-buffered stream
        x_tile = B * bk * x_bytes
        acc = B * bn * 4
        return w_tile + x_tile + acc <= vmem_budget

    best = (LANE, LANE)
    bk = min(K, 2048)
    while bk >= LANE:
        if K % bk == 0:
            bn = min(N, 2048)
            while bn >= LANE:
                if N % bn == 0 and fits(bk, bn):
                    if bk * bn > best[0] * best[1]:
                        best = (bk, bn)
                    break
                bn //= 2
        bk //= 2
    return best


def quantize_weight(w: jax.Array,
                    store_dtype=jnp.int8) -> Tuple[jax.Array, jax.Array]:
    """Absmax-quantize a (K, N) weight matrix per OUTPUT column.

    Returns ``(q, scale)``: ``q`` is (K, N) in ``store_dtype`` and
    ``scale`` is (N,) f32 — one scale per output tile column, applied at
    the kernel's f32 flush so the streamed bytes halve while the
    accumulation precision is unchanged.  All-zero columns get scale 0.
    """
    qmax = 127.0
    x = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=0)
    scale = amax / qmax
    y = x / jnp.where(scale > 0, scale, 1.0)[None, :]
    q = jnp.clip(jnp.round(y), -qmax, qmax).astype(store_dtype)
    return q, scale


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def gemv(x: jax.Array, w: jax.Array, b: Optional[jax.Array] = None, *,
         w_scale: Optional[jax.Array] = None, use_pallas: bool = True,
         interpret: Optional[bool] = None) -> jax.Array:
    """Decode GEMV: (B,K) x (K,N) -> (B,N), f32 accumulation.

    ``w_scale`` (N,) marks ``w`` as int8-quantized per output column;
    the kernel multiplies it into the f32 accumulator before the bias.
    """
    if not use_pallas:
        return gemv_ref(x, w, b, w_scale=w_scale)
    B, K = x.shape
    N = w.shape[1]
    bk, bn = plan_blocks(B, K, N, w_bytes=w.dtype.itemsize,
                         x_bytes=x.dtype.itemsize)
    # a dim that no LANE-aligned tile divides is taken whole: Mosaic
    # accepts a block dim that spans its array
    bk = K if K % LANE else bk
    bn = N if N % LANE else bn
    return gemv_pallas(x, w, b, w_scale=w_scale, block_k=bk, block_n=bn,
                       interpret=interpret)
