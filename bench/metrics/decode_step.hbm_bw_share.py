"""Whole decode step: bytes it has to move over peak HBM bandwidth, in %
(the LPU paper's own yardstick: a decode step should cost its weight
and KV bytes over bandwidth).

Bytes of every decode step of the window (``harness.flops_bytes.
step_bytes``: all weights once, the live KV blocks, the new K and V)
over the device time of the decode programs in the window (the
programs that run the paged decode kernel), over peak.

Source: the device trace (program time) and the benchmark's records of
the lengths in flight.  Moves ``tpot_p90_ms``.
"""
from harness import flops_bytes as fb

KERNEL = "paged_decode_attention"   # the decode programs are those
                                    # that run the paged kernel


def read(run):
    if run.trace is None or not run.peaks:
        return None
    t, n = run.trace.programs_with(KERNEL)
    steps = run.decode_ctx()
    if t <= 0 or n == 0 or not steps:
        return None
    nbytes = fb.over_steps(fb.step_bytes, run.dims, steps, run.block)
    return 100.0 * nbytes / t / run.peaks["hbm_bytes_per_s"]
