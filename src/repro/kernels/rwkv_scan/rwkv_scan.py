"""RWKV6 WKV recurrence as a Pallas TPU kernel.

The data-dependent-decay state update is a rank-1 outer-product
accumulation per head — on a GPU this is a per-warp shared-memory loop;
the TPU-native form keeps the (dh x dh) state matrix resident in VMEM
scratch per (batch, head-tile) while (r,k,v,w) stream through in seq
chunks (grid minor axis), and expresses each step as VPU outer products.
Like the LPU's generation stage, bytes moved = the streamed operands;
the state never touches HBM until the final flush.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
                s_ref, *, s_tiles: int, block_s: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0]

    u = u_ref[0]                                     # (dh,)

    def step(i, s):
        rt = r_ref[0, i, 0]                          # (dh,)
        kt = k_ref[0, i, 0]
        vt = v_ref[0, i, 0]
        wt = w_ref[0, i, 0]
        kv = kt[:, None] * vt[None, :]               # (dh, dh)
        y = jnp.sum((s + u[:, None] * kv) * rt[:, None], axis=0)
        y_ref[0, i, 0] = y
        return wt[:, None] * s + kv

    s = jax.lax.fori_loop(0, block_s, step, s_ref[...])
    s_ref[...] = s

    @pl.when(t == s_tiles - 1)
    def _flush():
        sout_ref[0, 0] = s_ref[...]


def rwkv_scan_pallas(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                     u: jax.Array, s0: jax.Array, *, block_s: int = 128,
                     interpret: Optional[bool] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """r,k,v,w: (B,S,H,dh) f32; u: (H,dh); s0: (B,H,dh,dh)."""
    B, S, H, dh = r.shape
    block_s = min(block_s, S)
    assert S % block_s == 0
    s_tiles = S // block_s

    kernel = functools.partial(_wkv_kernel, s_tiles=s_tiles,
                               block_s=block_s)
    y, s_fin = pl.pallas_call(
        kernel,
        grid=(B, H, s_tiles),
        in_specs=[
            pl.BlockSpec((1, block_s, 1, dh), lambda b, h, t: (b, t, h, 0)),
            pl.BlockSpec((1, block_s, 1, dh), lambda b, h, t: (b, t, h, 0)),
            pl.BlockSpec((1, block_s, 1, dh), lambda b, h, t: (b, t, h, 0)),
            pl.BlockSpec((1, block_s, 1, dh), lambda b, h, t: (b, t, h, 0)),
            pl.BlockSpec((1, dh), lambda b, h, t: (h, 0)),
            pl.BlockSpec((1, 1, dh, dh), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_s, 1, dh), lambda b, h, t: (b, t, h, 0)),
            pl.BlockSpec((1, 1, dh, dh), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, dh), r.dtype),
            jax.ShapeDtypeStruct((B, H, dh, dh), s0.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        interpret=interpret_mode(interpret),
        name="rwkv_scan",
    )(r, k, v, w, u, s0)
    return y, s_fin
