"""Engine step (``serving/engine.py``): host time of a ``step()`` call
that ran at least one prefill, in ms, mean over the window (the decode
window of the same call included: that is the stall a prefill causes).

Source: the benchmark's host clock around ``step()``, classified by the
program's counters.  Moves ``ttft_p90_ms``.
"""


def read(run):
    xs = [s.t1 - s.t0 for s in run.window_steps() if s.prefills > 0]
    return sum(xs) / len(xs) * 1e3 if xs else None
