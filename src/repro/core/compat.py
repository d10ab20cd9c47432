"""Mesh construction and ``shard_map`` for the manual-collective layer.

Every mesh and every ``shard_map`` wrap in the repo goes through this
module.  ``jax.make_mesh`` defaults to Explicit axis types; the ring code
(ppermute rings, grouped collectives inside ``shard_map``) is written
against Auto axes, so the mesh asks for them.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax import shard_map
from jax.sharding import AxisType

__all__ = ["make_mesh", "shard_map"]


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, devices=None):
    """``jax.make_mesh`` with Auto axis types."""
    kw = {} if devices is None else {"devices": devices}
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(AxisType.Auto,) * len(names), **kw)
