"""Whole decode step: model FLOP/s over peak bf16 FLOP/s, in %.

Operations of every decode step of the window (the configuration's
family, ``step_flops``: for OPT the projections, MLP and head per live
token, attention over its context) over the device time of the decode
programs in the window (the programs that run the family's
``DECODE_KERNEL``), over peak.  A later change that takes a kernel off
the path leaves that kernel's roofline silent; this share still bounds
the whole step.

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
    flops = over_steps(fam.step_flops, run.dims, steps)
    return 100.0 * flops / t / run.peaks["bf16_flops"]
