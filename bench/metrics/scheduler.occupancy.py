"""Scheduler (``serving/scheduler.py``, ``kv_cache.py``): the share of
slot-steps that held a live sequence over the window
(``EngineStats.busy_slot_steps / slot_steps``, deltas from the window's
open to its close).

Source: the program's counters.  Moves ``tokens_per_s``.
"""


def read(run):
    slot = run.stats1["slot_steps"] - run.stats0["slot_steps"]
    if slot <= 0:
        return None
    return (run.stats1["busy_slot_steps"]
            - run.stats0["busy_slot_steps"]) / slot
