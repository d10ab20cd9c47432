"""Pallas kernels for the compute hot-spots the paper optimizes.

Each kernel package holds ``<name>.py`` (the Pallas kernel), ``ops.py``
(the jitted wrapper) and ``ref.py`` (the pure-jnp oracle).
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a ``pallas_call`` runs in interpret mode.

    ``None`` (every entry point's default) decides from the backend:
    compiled Mosaic on a TPU, the Pallas interpreter everywhere else.  An
    explicit bool overrides it, e.g. to compile for a described TPU from
    a CPU process.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
