"""jit'd wrapper for the WKV kernel."""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax

from repro.kernels.rwkv_scan.ref import rwkv_scan_ref
from repro.kernels.rwkv_scan.rwkv_scan import rwkv_scan_pallas


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def rwkv_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
              u: jax.Array, s0: jax.Array, *, use_pallas: bool = True,
              interpret: Optional[bool] = None
              ) -> Tuple[jax.Array, jax.Array]:
    B, S, H, dh = r.shape
    if not use_pallas:
        return rwkv_scan_ref(r, k, v, w, u, s0)
    if S % 8:
        raise ValueError(f"rwkv_scan: S={S} must be a multiple of 8 "
                         "(use_pallas=False runs the oracle)")
    bs = 128
    while S % bs:
        bs //= 2
    return rwkv_scan_pallas(r, k, v, w, u, s0, block_s=max(bs, 8),
                            interpret=interpret)
