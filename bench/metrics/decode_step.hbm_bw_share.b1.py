"""``decode_step.hbm_bw_share`` in the batch-1 cell, where it moves
``tpot_p90_ms.b1``: the same reading (bytes of the window's decode
steps over their device time, over peak HBM bandwidth, in %)."""
from pathlib import Path

from harness.spec import metric_reader

BENCH = Path(__file__).resolve().parents[1]
read = metric_reader("decode_step.hbm_bw_share", BENCH)
