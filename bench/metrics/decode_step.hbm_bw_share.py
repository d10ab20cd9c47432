"""Whole decode step: bytes it has to move over peak HBM bandwidth, in %
(the LPU paper's own yardstick: a decode step should cost its weight
and KV bytes over bandwidth).

Bytes of every decode step of the window (the configuration's family,
``step_bytes``: for OPT all weights once, the live KV blocks, the new K
and V) over the device time of the decode programs in the window (the
programs that run the family's ``DECODE_KERNEL``), over peak.

Source: the device trace (program time) and the benchmark's records of
the lengths in flight.  Moves ``tpot_p90_ms``.
"""
from harness.families import over_steps


def read(run):
    if run.trace is None or not run.peaks:
        return None
    fam = run.family
    t, n = run.trace.programs_with(fam.DECODE_KERNEL)
    steps = run.decode_ctx()
    if t <= 0 or n == 0 or not steps:
        return None
    nbytes = over_steps(fam.step_bytes, run.dims, steps, run.block)
    return 100.0 * nbytes / t / run.peaks["hbm_bytes_per_s"]
