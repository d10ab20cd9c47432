"""One run of a cell with the serving program's span recorder attached.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> --record <0|1>

The run is a benchmark run (``bench/run.py``: the same set-up, warm-up,
traffic and window, through ``harness.cell``) with two differences:
``--record 1`` attaches ``repro.serving.tracker.SpanRecorder`` to the
engine before the window, and no reference check follows the window.

* ``--trace 0``: the cell's end-to-end metrics, so that runs with
  ``--record 1`` and ``--record 0`` give what recording costs.
* ``--trace 1 --record 1``: the readings of the program's spans
  (``harness.spans``): ``scheduler.queue_wait_ms_p90``,
  ``engine.prefill_ms``, ``engine.host_ms_per_decode_step``,
  ``device.idle_in_host_share``; the window's device idle time split by
  the innermost span over it; each decode-only step over twice the
  median, split the same way; the cell's existing per-layer metrics,
  read from the same trace; and the window's spans and device idle
  intervals those readings were computed from.

The last line of standard output is one JSON object.  Without a TPU it
exits 3.  The benchmark's own runs never run this script.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

from harness import cell as C  # noqa: E402
from harness import spans as S  # noqa: E402
from harness import trace as T  # noqa: E402
from harness.spec import (load_benchmark, load_peaks, metric_reader,  # noqa: E402
                          resolve_cell)


def span_readings(run: C.Run, rec, spans, ops, lo: float, hi: float) -> dict:
    """The readings of the program's spans in the window ``[lo, hi)``
    of the trace (``run.t0``/``run.t_close`` on the host clock)."""
    out = {"scheduler.queue_wait_ms_p90": S.queue_wait_ms_p90(
               rec.requests, run.t0, run.t_close),
           "engine.prefill_ms": S.prefill_ms(spans, lo, hi),
           "engine.host_ms_per_decode_step":
               S.host_ms_per_decode_step(spans, lo, hi),
           "device.idle_in_host_share":
               S.idle_in_host_share(spans, ops, lo, hi)}
    return {k: v for k, v in out.items() if v is not None}


def run_spans(cell, seed: int, seconds: float, trace: bool, record: bool,
              t_start: float, on_chip: bool = True) -> dict:
    import jax
    from repro.serving.tracker import SpanRecorder
    if on_chip:
        C.require_chips(cell.chips)
    engine, d = C.build(cell, seed, on_chip)
    n_warm = C.warm_up(engine, cell.traffic)
    gc.collect()
    gc.freeze()
    drv = C.Driver(engine, cell.traffic, seed, seconds, annotate=trace)
    log_dir = tempfile.mkdtemp(prefix="bench_spans_") if trace else None
    if trace:
        drv.on_close = jax.profiler.stop_trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    rec = SpanRecorder().attach(engine) if record else None
    setup_s = time.perf_counter() - t_start
    C.log(f"set-up {setup_s:.3f} s ({n_warm} warm-up requests); window "
          f"{seconds} s; recorder {'on' if record else 'off'}")
    try:
        asyncio.run(drv.drive())
    finally:
        if rec is not None:
            rec.detach()
    device = C.device_info(cell.chips)
    run = C.Run(cell=cell, dims=d, block=engine.block_size,
                seconds=seconds, reqs=drv.reqs, steps=drv.steps,
                token_times=drv.token_times, t0=drv.t0,
                t_close=drv.t_close, stats0=drv.stats0, stats1=drv.stats1)
    failed = sum(1 for r in run.reqs if r.status != "completed")
    result = {"workload": cell.name, "seed": seed, "trace": trace,
              "record": record, "attempted": len(run.reqs),
              "failed": failed}
    if trace:
        evs, spans = S.load(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
        lo, hi = T.window_bounds(evs, "bench.window_open",
                                 "bench.window_close")
        run.trace = T.reduce(evs, lo, hi, cell.chips)
        run.peaks = load_peaks(device["kind"]) if on_chip else {}
        plane = T.device_planes(evs)[0]
        ops = T.ops(evs, plane)
        result["per_layer"] = {
            m["name"]: v for m in cell.per_layer
            if (v := metric_reader(m["name"])(run)) is not None}
        if rec is not None:
            result["spans"] = span_readings(run, rec, spans, ops, lo, hi)
            gaps = S.idle_gaps(ops, lo, hi)
            split = S.split_by_innermost(spans, gaps)
            result["idle_split_s"] = split
            result["stalls"] = S.stalls(spans, ops, lo, hi)
            # what the readings above were computed from, for a later
            # reading: the window's spans and device idle intervals (ns
            # from the window's open)
            result["window_events"] = {
                "spans": [[s.name, s.start_ns - lo, s.dur_ns, s.counts]
                          for s in spans if s.end_ns > lo and s.start_ns < hi],
                "idle": [[a - lo, b - lo] for a, b in gaps]}
            C.log("device idle in the window by the innermost span over it "
                  f"(s): {json.dumps(split)}; idle "
                  f"{sum(b - a for a, b in gaps) * 1e-9:.3f} of "
                  f"{(hi - lo) * 1e-9:.3f} s")
            for st in result["stalls"]:
                C.log(f"stall: {st['ms']:.1f} ms step (median "
                      f"{st['median_ms']:.1f}) at {st['at_s']:.2f} s, "
                      f"device idle {st['device_idle_s']:.3f} s in it; by "
                      f"span {json.dumps(st['split_s'])}")
        result["breakdown"] = run.trace.breakdown
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    else:
        result["metrics"] = C.end_to_end(
            run, [m["name"] for m in cell.end_to_end])
        result["metrics"]["setup_s"] = setup_s
    result["device"] = device
    C.log(f"window: {len(run.reqs)} requests due, {failed} failed; "
          f"{len(run.token_times)} tokens in all")
    C.log_stalls(run)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--record", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    cell = resolve_cell(load_benchmark(BENCH.parent), args.workload,
                        BENCH.parent)
    try:
        C.require_chips(cell.chips)
    except C.NoChip as e:
        print(f"spans: {e}", file=sys.stderr)
        return 3
    from run import use_cache
    use_cache()
    result = run_spans(cell, args.seed, args.seconds, bool(args.trace),
                       bool(args.record), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
