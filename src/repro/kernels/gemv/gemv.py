"""Streamlined decode GEMV — the SXE/SMA dataflow as a Pallas TPU kernel.

LPU C1: during generation the operand is one activation *vector* per
sequence; performance == how fast weights stream HBM -> compute.  The
kernel keeps the activation block **stationary in VMEM** (the LPU's
register-file operand) while weight tiles stream through a
(K_blk, N_blk) VMEM window (the SMA burst), accumulating
output-stationary f32 partials in scratch — the MAC-tree accumulator.

Grid: (N_tiles, K_tiles); K is the *minor* (fastest) axis so each output
tile sees its full reduction before the next begins — the paper's
"vertical tile order [that] reduces partial-sum buffers".

Tile sizing (ops.py): the (K_blk, N_blk) window is chosen so the weight
stream saturates HBM while fitting VMEM — the LPU's
``I x v x 2B x freq = BW`` balance condition expressed as a BlockSpec.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _gemv_kernel(x_ref, w_ref, b_ref, s_ref, o_ref, acc_ref, *,
                 k_tiles: int, has_bias: bool, has_scale: bool):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)          # (B, K_blk)  stationary
    w = w_ref[...].astype(jnp.float32)          # (K_blk, N_blk) streamed
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == k_tiles - 1)
    def _flush():
        acc = acc_ref[...]
        if has_scale:
            # int8 weight tiles: one absmax scale per output column,
            # applied ONCE at the f32 flush (before the fp bias) so the
            # stream stays quantized end to end
            acc = acc * s_ref[...].astype(jnp.float32)
        if has_bias:
            acc = acc + b_ref[...].astype(jnp.float32)
        o_ref[...] = acc.astype(o_ref.dtype)


def gemv_pallas(x: jax.Array, w: jax.Array, b: jax.Array | None = None, *,
                w_scale: jax.Array | None = None,
                block_n: int = 512, block_k: int = 512,
                interpret: Optional[bool] = None) -> jax.Array:
    """x: (B, K); w: (K, N); optional b: (N,) -> (B, N).

    B (decode batch per device) stays whole — it is tiny by design.
    ``w_scale`` (N,) marks ``w`` as int8 per-output-column quantized;
    the scale tile rides the same (1, N_blk) window as the bias.
    """
    B, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    block_k = min(block_k, K)
    block_n = min(block_n, N)
    assert K % block_k == 0 and N % block_n == 0, (K, N, block_k, block_n)
    k_tiles = K // block_k
    n_tiles = N // block_n
    has_bias = b is not None
    has_scale = w_scale is not None
    if b is None:
        b = jnp.zeros((N,), x.dtype)
    b2 = b.reshape(1, N)
    s2 = (w_scale if w_scale is not None
          else jnp.ones((N,), jnp.float32)).reshape(1, N)

    kernel = functools.partial(_gemv_kernel, k_tiles=k_tiles,
                               has_bias=has_bias, has_scale=has_scale)
    return pl.pallas_call(
        kernel,
        grid=(n_tiles, k_tiles),
        in_specs=[
            pl.BlockSpec((B, block_k), lambda n, k: (0, k)),
            pl.BlockSpec((block_k, block_n), lambda n, k: (k, n)),
            pl.BlockSpec((1, block_n), lambda n, k: (0, n)),
            pl.BlockSpec((1, block_n), lambda n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((B, block_n), lambda n, k: (0, n)),
        out_shape=jax.ShapeDtypeStruct((B, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((B, block_n), jnp.float32)],
        interpret=interpret_mode(interpret),
        name="gemv",
    )(x, w, b2, s2)
