"""jit'd wrapper for the selective scan kernel."""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax

from repro.kernels.mamba_scan.mamba_scan import mamba_scan_pallas
from repro.kernels.mamba_scan.ref import mamba_scan_ref


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def mamba_scan(da: jax.Array, bx: jax.Array, c: jax.Array, h0: jax.Array,
               *, use_pallas: bool = True,
               interpret: Optional[bool] = None
               ) -> Tuple[jax.Array, jax.Array]:
    B, S, C, N = da.shape
    if not use_pallas:
        return mamba_scan_ref(da, bx, c, h0)
    if S % 8 or C % 8:
        raise ValueError(f"mamba_scan: S={S} and C={C} must be multiples "
                         "of 8 (use_pallas=False runs the oracle)")
    bs = 128
    while S % bs:
        bs //= 2
    bc = 128
    while C % bc:
        bc //= 2
    return mamba_scan_pallas(da, bx, c, h0, block_s=max(bs, 8),
                             block_c=max(bc, 8), interpret=interpret)
