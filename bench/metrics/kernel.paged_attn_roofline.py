"""Kernel (``kernels/decode_attention``, the streamed Pallas paged decode
kernel): its share of its roofline, in %.

The least time the chip could take for the kernel's work in the window
is the larger of its bytes over peak HBM bandwidth and its operations
over peak bf16 FLOP/s; bytes and operations come from the
configuration's family (``paged_attn_bytes``, ``paged_attn_flops``: for
OPT the live slots' KV blocks, queries and outputs), per layer, per
decode step of the window, from the lengths the benchmark recorded in
flight.  That over the summed device time of the kernel's events (the
family's ``DECODE_KERNEL``).  At decode the bytes bind (the kernel does
about one multiply-add per KV byte), so this is a bandwidth share.

Source: the device trace.  Moves ``tpot_p90_ms``.
"""
from harness.families import over_steps


def read(run):
    if run.trace is None or not run.peaks:
        return None
    fam = run.family
    t, calls = run.trace.ops(fam.DECODE_KERNEL)
    steps = run.decode_ctx()
    if t <= 0 or calls == 0 or not steps:
        return None
    L = run.dims["L"]
    nbytes = L * over_steps(fam.paged_attn_bytes, run.dims, steps,
                            run.block)
    flops = L * over_steps(fam.paged_attn_flops, run.dims, steps)
    least = max(nbytes / run.peaks["hbm_bytes_per_s"],
                flops / run.peaks["bf16_flops"])
    return 100.0 * least / t
