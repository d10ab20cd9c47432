"""Model families: all the benchmark knows of one architecture.

A configuration file names its family (``"family": "opt"``), and
:func:`harness.spec.load_family` loads ``harness/families/<family>.py``
by its path.  The harness reaches the model only through that module,
which provides:

``dims(model) -> dict``
    the sizes the benchmark needs, from the configuration's ``model``
    keys.  Among them ``L``, the layers whose decode calls
    ``DECODE_KERNEL`` once each per decode step, and ``V``, the
    vocabulary every served id lies in.
``arch_config(conf, d)``
    the serving program's ``ArchConfig`` for the configuration ``conf``.
``canonical(key, d, dtype=jnp.bfloat16)``
    every weight from a PRNG key, in the family's own layout (jittable:
    the benchmark makes them on the device in one call).
``program_params(c, d, template)``
    the serving program's parameter tree from those weights;
    ``template`` is the tree's shapes, and a leaf that does not match
    it is an error.
``served_gaps(c, d, seqs, control=False)``
    the plain float32 reference, teacher-forced on ``(prompt, served)``
    pairs: at every served position, the gap between its best logit and
    its logit for the served token, and with ``control`` the gap of the
    token the control puts first; the control is the reference one
    precision below the one the configuration serves in, which the
    family picks.  It imports nothing of the program.
``DECODE_KERNEL``
    the device op name of the paged decode kernel; the decode programs
    are those that run it.  Where a family's decode runs no such op, the
    kernel and whole-step readers find nothing and report nothing.
``step_flops(d, ctx)``, ``step_bytes(d, ctx, block)``
    operations and bytes of one whole decode step, ``ctx`` the resident
    token counts of the live slots.
``paged_attn_flops(d, ctx)``, ``paged_attn_bytes(d, ctx, block)``
    one layer's call of ``DECODE_KERNEL`` at one decode step.

A new architecture comes as new files: ``bench/configs/<c>.json``, this
module for its family, its traffic, limit and metric-reader files, and
entries in ``BENCHMARK.json``.  The harness does not change.

What every family shares is here: the key a seed gives (one seed, one
key, for every model) and a count summed over decode steps.
"""
from __future__ import annotations

from typing import Callable, Dict, Iterable, Sequence

import jax


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative integer (the low 31 bits seed
    it, the rest are folded in)."""
    if seed < 0:
        raise ValueError("seed must be >= 0")
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)


def over_steps(fn: Callable, d: Dict[str, int],
               steps: Iterable[Sequence[int]], *args) -> int:
    """``fn(d, ctx, *args)`` summed over the decode steps ``steps``."""
    return sum(fn(d, ctx, *args) for ctx in steps)
