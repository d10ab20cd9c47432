"""The readings of the program's spans, on hand-made events with known
answers, the trace loader on a trace recorded here, and a whole run of
``bench/spans.py`` on the CPU at a tiny size."""
import time
from types import SimpleNamespace

import numpy as np
import pytest

from harness import spans as S
from harness import trace as T
from test_bench_faults import tiny_cell

DEV = "/device:TPU:0"


def op(s, d, name="fusion.1"):
    return T.Ev(DEV, T.OPS_LINE, name, float(s), float(d))


def sp(name, s, d, **counts):
    return S.Span(name, float(s), float(d), counts)


# Two decode-only steps and one with a prefill, in a window [0, 1000):
#   lpu.step [0, 300) steps 2: waits [100, 200), host work 200 ns;
#   lpu.step [300, 700) prefills 1 (left out of the per-step reading);
#   lpu.step [700, 900) steps 1: wait [750, 850), host work 100 ns.
# The device runs [100, 200), [350, 600) and [750, 850).
SPANS = [
    sp("bench.engine_step", 0, 300),
    sp("lpu.step", 0, 300, prefills=0, steps=2),
    sp("lpu.schedule", 10, 40),
    sp("lpu.decode", 50, 250, steps=2),
    sp("lpu.wait", 100, 100, bytes=8),
    sp("py.gc", 220, 30, generation=0),
    sp("lpu.step", 300, 400, prefills=1, steps=0),
    sp("lpu.prefill", 310, 200, rid=5, tokens=64, bucket=64),
    sp("lpu.prefill", 520, 100, rid=6, tokens=16, bucket=16),
    sp("lpu.step", 700, 200, prefills=0, steps=1),
    sp("lpu.wait", 750, 100, bytes=8),
]
OPS = [op(100, 100), op(350, 250), op(750, 100)]


def test_host_ms_per_decode_step():
    # (300 - 100) + (200 - 100) ns over 3 decode steps
    assert S.host_ms_per_decode_step(SPANS, 0, 1000) == pytest.approx(
        300 / 3 * 1e-6)
    assert S.host_ms_per_decode_step(SPANS, 300, 700) is None


def test_prefill_ms_is_a_mean_per_prompt():
    assert S.prefill_ms(SPANS, 0, 1000) == pytest.approx(150 * 1e-6)
    chunked = SPANS + [sp("lpu.prefill", 630, 50, rid=5)]
    assert S.prefill_ms(chunked, 0, 1000) == pytest.approx(175 * 1e-6)


def test_idle_in_host_share():
    # idle: [0,100) [200,350) [600,750) [850,1000) = 550 ns.  The host
    # is in lpu spans other than a wait over [0,100) [200,300)
    # [300,350) [600,700) [700,750) [850,900): 100+100+50+100+50+50
    assert S.idle_in_host_share(SPANS, OPS, 0, 1000) == pytest.approx(45.0)
    assert S.idle_in_host_share([], OPS, 0, 1000) is None


def test_idle_split_by_innermost_span():
    gaps = S.idle_gaps(OPS, 0, 1000)
    assert gaps == [(0, 100), (200, 350), (600, 750), (850, 1000)]
    split = S.split_by_innermost(SPANS, gaps)
    # [0,10) step; [10,50) schedule; [50,100) decode | [200,220) decode;
    # [220,250) gc; [250,300) decode; [300,310) step; [310,350) prefill |
    # [600,620) prefill; [620,750) step | [850,900) step; [900,1000)
    # outside every span
    want = {"lpu.step": 10 + 10 + 130 + 50, "lpu.schedule": 40,
            "lpu.decode": 50 + 20 + 50, "py.gc": 30, "lpu.prefill": 40 + 20,
            "outside_spans": 100}
    assert {k: round(v * 1e9) for k, v in split.items()} == want
    assert sum(split.values()) == pytest.approx(550e-9)


def test_stalls_name_the_span_they_sat_in():
    steps = [sp("lpu.step", 1000 * i, 100, prefills=0, steps=1)
             for i in range(5)]
    waits = [sp("lpu.wait", 1000 * i + 20, 60) for i in range(5)]
    stall = [sp("lpu.step", 5000, 900, prefills=0, steps=1),
             sp("py.gc", 5100, 700, generation=2),
             sp("lpu.wait", 5850, 40)]
    spans = sorted(steps + waits + stall, key=lambda s: s.start_ns)
    ops = [op(1000 * i + 20, 60) for i in range(6)]
    got = S.stalls(spans, ops, 0, 6000)
    assert len(got) == 1
    st = got[0]
    assert st["ms"] == pytest.approx(900e-6)
    assert st["median_ms"] == pytest.approx(100e-6)
    assert max(st["split_s"], key=st["split_s"].get) == "py.gc"
    assert st["split_s"]["py.gc"] == pytest.approx(700e-9)
    # the device ran [5020, 5080) only: idle the rest of the step
    assert st["device_idle_s"] == pytest.approx(840e-9)


def test_queue_wait_p90_over_the_window_requests():
    def q(s, e):
        return SimpleNamespace(name="lpu.request.queue", start=s, end=e)

    reqs = {i: [q(10.0 + i, 10.0 + i + 0.001 * i),
                SimpleNamespace(name="lpu.request.decode", start=0, end=9)]
            for i in range(1, 11)}
    reqs[3].append(q(20.0, 20.010))                  # preempted, queued again
    reqs[99] = [q(5.0, 6.0)]                        # before the window
    got = S.queue_wait_ms_p90(reqs, 10.0, 30.0)
    waits = [float(i) for i in range(1, 11) if i != 3] + [13.0]
    assert got == pytest.approx(float(np.percentile(waits, 90)))
    assert S.queue_wait_ms_p90(reqs, 40.0, 50.0) is None


def test_loader_keeps_what_the_trace_reader_keeps(tmp_path):
    import jax
    from repro.serving.tracker import SpanRecorder
    rec = SpanRecorder()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window_open"):
        pass
    with rec.span("lpu.step", ring=0) as s:
        with rec.span("lpu.wait", bytes=4):
            jax.numpy.ones(8).block_until_ready()
        s.count(steps=1, prefills=0)
    with jax.profiler.TraceAnnotation("bench.window_close"):
        pass
    jax.profiler.stop_trace()
    evs, spans = S.load(str(tmp_path))
    assert evs == T.load_xplane(str(tmp_path))
    names = [x.name for x in spans]
    assert names == ["bench.window_open", "lpu.step", "lpu.wait",
                     "bench.window_close"]
    step = spans[1]
    assert step.counts == {"ring": 0, "steps": 1, "prefills": 0}
    assert spans[2].counts == {"bytes": 4}
    assert step.start_ns <= spans[2].start_ns <= spans[2].end_ns \
        <= step.end_ns


@pytest.mark.parametrize("record", [False, True])
def test_spans_run_on_the_cpu(record):
    import spans as script
    r = script.run_spans(tiny_cell("tiny-open"), 3_100_000_007, 2.0, False,
                         record, time.perf_counter(), on_chip=False)
    assert r["record"] is record and r["failed"] == 0
    assert r["attempted"] == 8
    assert {"tokens_per_s", "ttft_p90_ms", "tpot_p90_ms",
            "setup_s"} <= set(r["metrics"])
