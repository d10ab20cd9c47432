"""Operations and bytes of an OPT decode step, from shapes and the
context lengths in flight.

``ctx`` is the list of resident token counts of the live slots at one
decode step (each slot attends its ``p`` resident tokens plus the new
one).  Bytes are what the algorithm has to move, not what a kernel
happens to move: weights once per step, the live KV blocks once per
layer, and the new token's K and V written once.  The vocabulary is
``V`` rows (the program's padding is not work).
"""
from __future__ import annotations

from typing import Dict, Iterable, Sequence


def weight_params(d: Dict[str, int]) -> int:
    """Parameters one decode step streams (head included, position
    table and embedding gather excluded: one row each)."""
    L, D, F, V = d["L"], d["D"], d["F"], d["V"]
    per_layer = 4 * D * D + 2 * D * F + 4 * D + F + D + 4 * D
    return L * per_layer + V * D + 2 * D


def matmul_params(d: Dict[str, int]) -> int:
    """Parameters that take part in a multiply-add per token."""
    L, D, F, V = d["L"], d["D"], d["F"], d["V"]
    return L * (4 * D * D + 2 * D * F) + V * D


def blocks(p: int, block: int) -> int:
    return -(-p // block)


def kv_read_bytes(d: Dict[str, int], ctx: Sequence[int], block: int,
                  itemsize: int = 2) -> int:
    """K and V of the live slots' resident blocks, every layer."""
    per_tok = 2 * d["D"] * itemsize
    return d["L"] * sum(blocks(p, block) * block for p in ctx) * per_tok


def step_bytes(d: Dict[str, int], ctx: Sequence[int], block: int,
               itemsize: int = 2) -> int:
    """Bytes one decode step has to move from or to HBM."""
    write = d["L"] * len(ctx) * 2 * d["D"] * itemsize
    return (weight_params(d) * itemsize + kv_read_bytes(d, ctx, block,
                                                        itemsize) + write)


def step_flops(d: Dict[str, int], ctx: Sequence[int]) -> int:
    """Model operations of one decode step (2 per multiply-add): the
    projections, MLP and head for every live token, and attention over
    ``p + 1`` positions (scores and weighted sum)."""
    attn = sum(4 * (p + 1) * d["D"] for p in ctx) * d["L"]
    return 2 * matmul_params(d) * len(ctx) + attn


def paged_attn_bytes(d: Dict[str, int], ctx: Sequence[int], block: int,
                     itemsize: int = 2) -> int:
    """One layer of the paged decode kernel: the live slots' KV blocks,
    and their queries and outputs."""
    kv = sum(blocks(p, block) * block for p in ctx) * 2 * d["D"] * itemsize
    return kv + 2 * len(ctx) * d["D"] * itemsize


def paged_attn_flops(d: Dict[str, int], ctx: Sequence[int]) -> int:
    """One layer of the paged decode kernel."""
    return sum(4 * (p + 1) * d["D"] for p in ctx)


def over_steps(fn, d, steps: Iterable[Sequence[int]], *args) -> int:
    return sum(fn(d, ctx, *args) for ctx in steps)
