"""Readings a cell's correctness limit is set from, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, one run of the cell at its own load (a short window, the
mix's longest requests drained as in a benchmark run), then the plain
float32 reference over a seeded sample of the served tokens, and the
control over the same positions: the reference one precision below the
one the configuration serves in, as its family picks it (for OPT every
matrix product computed in int8, below the bfloat16 served).  The
control stands in the program's place in the run's own comparison, so
each of its runs has to come out not correct.  Prints, per seed, the
program's widest served-logit gap (the lower reading) and the
control's (the upper reading), then one JSON line with both lists;
exits 1 where a control run came out correct.

The benchmark's own runs never run the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

from harness.spec import load_benchmark, resolve_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = resolve_cell(load_benchmark(BENCH.parent), args.workload,
                        BENCH.parent)
    from harness.cell import NoChip, require_chips, run_cell
    try:
        require_chips(cell.chips)
    except NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    from run import use_cache
    use_cache()
    program, control, rows = [], [], []
    for s in (int(x) for x in args.seeds.split(",")):
        r = run_cell(cell, s, args.seconds, False, time.perf_counter(),
                     control=True)
        g, limit = r["checks"]["program_logit_gap"]
        c = r["checks"]["served_logit_gap"][0]
        n = r["checks"]["served_tokens_compared"][0]
        program.append(g)
        control.append(c)
        rows.append({"seed": s, "program": g, "control": c, "limit": limit,
                     "tokens": n, "correct": r["correct"],
                     "failed": r["failed"]})
        print(f"control {args.workload} seed {s}: program gap {g} control "
              f"gap {c} limit {limit} over {n} tokens; correct "
              f"{r['correct']}", file=sys.stderr, flush=True)
    caught = all(not row["correct"] for row in rows)
    print(json.dumps({"workload": args.workload, "rows": rows,
                      "lower": max(program), "upper": min(control),
                      "control_not_correct": caught,
                      "seconds": time.perf_counter() - T_START}))
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
