"""A model family whose operation and byte counts are twice OPT's and
whose decode kernel is OPT's: on the same trace the share readers read
twice what they read for OPT."""
from harness.spec import load_family

_opt = load_family("opt")
DECODE_KERNEL = _opt.DECODE_KERNEL


def step_flops(d, ctx):
    return 2 * _opt.step_flops(d, ctx)


def step_bytes(d, ctx, block, itemsize=2):
    return 2 * _opt.step_bytes(d, ctx, block, itemsize)


def paged_attn_flops(d, ctx):
    return 2 * _opt.paged_attn_flops(d, ctx)


def paged_attn_bytes(d, ctx, block, itemsize=2):
    return 2 * _opt.paged_attn_bytes(d, ctx, block, itemsize)
