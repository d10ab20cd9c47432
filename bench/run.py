"""Run one cell of the serving benchmark on the chip(s) of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``<cell>`` is a ``workloads`` name of ``BENCHMARK.json``.  The run builds
the cell's configuration with weights made from ``--seed``, warms up
every program the cell's traffic reaches (set-up), serves that traffic
for ``--seconds`` through the engine's async front end, drains what was
due, and holds a seeded sample of the served tokens to the plain
float32 reference.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared, with its limit.  The same numbers end
standard error.

Without a TPU, or with fewer chips than the cell needs, it exits 3 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))

from harness.spec import SpecError, load_benchmark, resolve_cell  # noqa: E402


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_cache() -> str:
    """JAX's persistent compilation cache, in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), for every program however
    quickly it compiles, so that only a cell's first run compiles."""
    import jax
    from repro.launch.compile_cache import use_compile_cache
    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    args = parse(argv)
    if args.seed < 0:
        print("bench: --seed must be >= 0", file=sys.stderr)
        return 2
    try:
        cell = resolve_cell(load_benchmark(ROOT), args.workload, ROOT)
    except (SpecError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    from harness.cell import NoChip, require_chips, run_cell
    try:
        require_chips(cell.chips)
    except NoChip as e:
        print(f"bench: {e}; this benchmark measures on the chip only",
              file=sys.stderr)
        return 3
    cache = use_cache()
    print(f"[bench] {args.workload} seed {args.seed}; compile cache {cache}",
          file=sys.stderr, flush=True)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      T_START)
    for k, (v, lim) in result["checks"].items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
