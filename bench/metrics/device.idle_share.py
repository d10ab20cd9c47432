"""Device (TPU v5e): the share of the traced window in which no
operation ran on the device, in %, averaged over the chips used.

Source: the device trace (1 - union of ``XLA Ops`` intervals / window).
Moves ``tokens_per_s``.
"""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
