"""Selective-scan (Mamba S6) Pallas kernel.

TPU adaptation of the CUDA selective-scan: instead of one thread block
per channel chunk with warp-level scans, we give each (batch, channel
tile) a *sequential walk over seq chunks* (grid minor axis) with the
recurrent state held in VMEM scratch — the TPU idiom for carried state
(same pattern as the LPU's output-stationary accumulators).  Within a
chunk the recurrence is a short fori_loop over VREG-resident rows; the
channel tile (C_blk x N) keeps the VPU lanes full.

Streaming structure mirrors C1: (da, bx, c) tiles stream HBM->VMEM once,
state never leaves VMEM — byte traffic is exactly the input size, i.e.
the kernel sits on the bandwidth roofline like everything else in the
decode path.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _scan_kernel(da_ref, bx_ref, c_ref, h0_ref, y_ref, hout_ref, h_ref,
                 *, s_tiles: int, block_s: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        h_ref[...] = h0_ref[0]

    da = da_ref[0]                                   # (block_s, C_blk, N)
    bx = bx_ref[0]
    cc = c_ref[0]                                    # (block_s, N)

    def step(i, h):
        h = da[i] * h + bx[i]                        # (C_blk, N)
        y_ref[0, i] = jnp.sum(h * cc[i][None, :], axis=-1)
        return h

    h = jax.lax.fori_loop(0, block_s, step, h_ref[...])
    h_ref[...] = h

    @pl.when(t == s_tiles - 1)
    def _flush():
        hout_ref[0] = h_ref[...]


def mamba_scan_pallas(da: jax.Array, bx: jax.Array, c: jax.Array,
                      h0: jax.Array, *, block_s: int = 128,
                      block_c: int = 128,
                      interpret: Optional[bool] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """da, bx: (B,S,C,N); c: (B,S,N); h0: (B,C,N) -> (y (B,S,C), h (B,C,N))."""
    B, S, C, N = da.shape
    block_s = min(block_s, S)
    block_c = min(block_c, C)
    assert S % block_s == 0 and C % block_c == 0
    s_tiles = S // block_s
    c_tiles = C // block_c

    kernel = functools.partial(_scan_kernel, s_tiles=s_tiles,
                               block_s=block_s)
    y, h_fin = pl.pallas_call(
        kernel,
        grid=(B, c_tiles, s_tiles),
        in_specs=[
            pl.BlockSpec((1, block_s, block_c, N),
                         lambda b, cb, t: (b, t, cb, 0)),
            pl.BlockSpec((1, block_s, block_c, N),
                         lambda b, cb, t: (b, t, cb, 0)),
            pl.BlockSpec((1, block_s, N), lambda b, cb, t: (b, t, 0)),
            pl.BlockSpec((1, block_c, N), lambda b, cb, t: (b, cb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_s, block_c), lambda b, cb, t: (b, t, cb)),
            pl.BlockSpec((1, block_c, N), lambda b, cb, t: (b, cb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, C), da.dtype),
            jax.ShapeDtypeStruct((B, C, N), h0.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_c, N), jnp.float32)],
        interpret=interpret_mode(interpret),
        name="mamba_scan",
    )(da, bx, c, h0)
    return y, h_fin
