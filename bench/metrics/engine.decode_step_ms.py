"""Engine step (``serving/engine.py``): host time per decode step, in ms,
over the ``step()`` calls of the window that ran no prefill (their
summed time over the decode steps they reconciled, so the reading spans
seconds of host clock).

Source: the benchmark's host clock around ``step()``, classified by the
program's counters.  Moves ``tpot_p90_ms``.
"""


def read(run):
    t = n = 0
    for s in run.window_steps():
        if s.prefills == 0 and s.steps > 0:
            t += s.t1 - s.t0
            n += s.steps
    return t / n * 1e3 if n else None
