"""``engine.decode_step_ms`` in the batch-1 cell, where it moves
``tpot_p90_ms.b1``: the same reading (host time per decode step of the
window's ``step()`` calls that ran no prefill, in ms)."""
from pathlib import Path

from harness.spec import metric_reader

BENCH = Path(__file__).resolve().parents[1]
read = metric_reader("engine.decode_step_ms", BENCH)
