"""Tracker protocol units + the EngineStats delta-accounting contract.

The schema tests need no model; the end-to-end delta tests drive a real
engine (and a chaos fleet) and lock the property the tracker seam
depends on: cumulative ``EngineStats`` counters are MONOTONE — even
across ``reset()``/ring rebuilds, where the engine banks subsystem
counter bases (the regression this PR fixes: preemptions, evictions and
the prefix counters used to restart from zero after a migration, so
per-window deltas went negative).
"""
import asyncio
import json
import math

import jax
import pytest

from repro.compiler.mapper import plan_model
from repro.configs import get_config
from repro.models.registry import build_model
from repro.serving.config import EngineConfig
from repro.serving.engine import EngineStats, LPUEngine, MultiRingEngine
from repro.serving.frontend import AsyncFrontend
from repro.serving.tracker import (NO_SPANS, EngineTap, JsonlTracker,
                                   NullTracker, RequestTimeline,
                                   RingBufferTracker, SpanRecorder,
                                   counter_fields, read_jsonl,
                                   snapshot_stats, stats_delta,
                                   validate_record)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("smollm-135m").reduced()
    plan = plan_model(cfg, None, (1,), "serve", esl_overlap=False,
                      remat="none", compute_dtype="float32",
                      param_dtype="float32")
    model = build_model(cfg, plan)
    params, _ = model.init(jax.random.PRNGKey(0))
    return model, params


# -- schema / sinks (no model) -----------------------------------------


WINDOW_REC = {"kind": "engine_window", "t": 1.0, "ring": 0, "step": 3,
              "dt_ms": 2.5, "delta": {"steps": 1, "tokens": 2}}
REQ_REC = {"kind": "request", "t": 2.0, "rid": 7, "status": "completed",
           "tokens": 5, "ttft_ms": 12.0, "ms_per_token": 3.0}


def test_validate_record_rejects_malformed():
    validate_record(WINDOW_REC)
    validate_record(REQ_REC)
    validate_record({"kind": "event", "t": 0.0, "name": "x"})
    for bad in (
        {"kind": "nope", "t": 0.0},
        {"kind": "engine_window", "t": float("nan"), "ring": 0,
         "step": 0, "dt_ms": 0.0, "delta": {}},
        {**WINDOW_REC, "delta": {"steps": -1}},      # regressed counter
        {k: v for k, v in REQ_REC.items() if k != "ttft_ms"},
        {**REQ_REC, "status": "exploded"},
        "not a dict",
    ):
        with pytest.raises(ValueError):
            validate_record(bad)


def test_jsonl_sink_round_trips(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with JsonlTracker(path) as tr:
        tr.log(WINDOW_REC)
        tr.log(REQ_REC)
        with pytest.raises(ValueError):
            tr.log({"kind": "request", "t": 0.0})   # invalid: not written
    assert tr.written == 2
    back = read_jsonl(path)
    assert back == [WINDOW_REC, REQ_REC]
    # every line is standalone JSON (the artifact contract)
    with open(path) as fh:
        for line in fh:
            json.loads(line)


def test_ring_buffer_windows_correctly():
    tr = RingBufferTracker(capacity=3)
    for i in range(5):
        tr.log({"kind": "event", "t": float(i), "name": f"e{i}"})
    assert tr.seen == 5
    assert [r["name"] for r in tr.records()] == ["e2", "e3", "e4"]
    assert [r["name"] for r in tr.window(2)] == ["e3", "e4"]
    assert [r["name"] for r in tr.window(99)] == ["e2", "e3", "e4"]
    assert tr.window(0) == []
    with pytest.raises(ValueError):
        RingBufferTracker(0)
    NullTracker().log(WINDOW_REC)                   # silently fine


def test_request_timeline_ttft_monotone():
    tl = RequestTimeline(1, t_submit=10.0, tenant="a")
    ts = [10.4, 10.5, 10.7, 11.0]
    for t in ts:
        tl.on_token(t)
    assert tl.ttft_ms == pytest.approx(400.0)
    # ms/token averages the post-first-token gaps only
    assert tl.ms_per_token == pytest.approx((11.0 - 10.4) * 1e3 / 3)
    rec = tl.record("completed", 11.0)
    validate_record(rec)
    assert rec["tenant"] == "a" and rec["tokens"] == 4
    # TTFT can never exceed total latency
    assert rec["ttft_ms"] <= (11.0 - 10.0) * 1e3
    # a tokenless (cancelled-before-prefill) timeline stays schema-valid
    empty = RequestTimeline(2, 0.0).record("cancelled", 1.0)
    validate_record(empty)
    assert empty["ttft_ms"] == -1.0 and empty["tokens"] == 0


def test_stats_delta_monotone_contract():
    a = {"steps": 1, "tokens": 4}
    b = {"steps": 3, "tokens": 9}
    assert stats_delta(a, b) == {"steps": 2, "tokens": 5}
    with pytest.raises(ValueError):
        stats_delta(b, a)                           # regression must raise
    # gauges are excluded from the counter set
    names = counter_fields(EngineStats())
    assert "peak_pool_blocks" not in names and "wall" not in names
    assert "tokens" in names and "preemptions" in names


# -- delta accounting against a real engine ----------------------------


def _run_with_tap(engine_or_fleet, engines, prompts, max_new):
    """Step to drain, emitting per-window deltas; returns per-engine
    delta sums keyed like the snapshots."""
    sink = RingBufferTracker(4096)
    taps = [EngineTap(e, ring=i) for i, e in enumerate(engines)]
    sums = [dict.fromkeys(counter_fields(e.stats), 0) for e in engines]
    for p in prompts:
        engine_or_fleet.submit(list(p), max_new)
    while engine_or_fleet.has_work():
        engine_or_fleet.step()
        for tap, acc in zip(taps, sums):
            rec = tap.emit(sink, t=0.0)
            if rec is not None:
                for k, v in rec["delta"].items():
                    acc[k] += v
    engine_or_fleet.drain()
    # one final emit catches counters drain() itself touched
    for tap, acc in zip(taps, sums):
        rec = tap.emit(sink, t=0.0)
        if rec is not None:
            for k, v in rec["delta"].items():
                acc[k] += v
    return sums, sink


def test_deltas_sum_to_cumulative_single_engine(tiny_model):
    model, params = tiny_model
    eng = LPUEngine(model, params, EngineConfig(
        slots=2, max_seq=64, paged=True, block_size=16,
        prefix_cache=True))
    sums, sink = _run_with_tap(eng, [eng],
                               [[1, 2, 3], [4, 5], [1, 2, 3, 9]], 6)
    final = snapshot_stats(eng.stats)
    assert sums[0] == final
    assert eng.stats.tokens > 0
    assert all(r["dt_ms"] >= 0 for r in sink.records())


def test_deltas_survive_reset_regression(tiny_model):
    # THE regression test: chaos kills ring 0 mid-flight; the rebuilt
    # scheduler/pool/prefix restart their counters at zero, but the
    # banked bases must keep cumulative EngineStats monotone — every
    # emitted delta >= 0 (EngineTap raises otherwise) and the sums
    # still equal the final cumulative counters
    model, params = tiny_model
    shared = list(range(1, 33))
    prompts = [shared + [50], shared + [51], [4, 5, 6], shared + [52]]
    fleet = MultiRingEngine(model, params, None, rings=2,
                            config=EngineConfig(
                                slots=2, max_seq=64, paged=True,
                                block_size=16, prefix_cache=True,
                                chaos="ring@2"))
    sums, _ = _run_with_tap(fleet, fleet.engines, prompts, 8)
    assert sum(e.stats.ring_failures for e in fleet.engines) >= 1
    for eng, acc in zip(fleet.engines, sums):
        assert acc == snapshot_stats(eng.stats)
    # the prefix counters kept counting across the rebuild: lookups on
    # the failed ring resume from the banked base, never below it
    hit = [e for e in fleet.engines if e.stats.ring_failures]
    assert hit and all(e.stats.prefix_lookups >= 0 for e in hit)


def test_cumulative_counters_never_regress_across_reset(tiny_model):
    # direct unit on the engine fix, no fleet: preempt + evict + prefix
    # traffic, snapshot, reset(), then verify no assigned counter went
    # backwards on the next step
    model, params = tiny_model
    eng = LPUEngine(model, params, EngineConfig(
        slots=2, max_seq=64, paged=True, block_size=16,
        prefix_cache=True))
    eng.generate([[1, 2, 3, 4] * 4, [1, 2, 3, 4] * 4 + [9]], 8)
    before = snapshot_stats(eng.stats)
    assert before["prefix_lookups"] > 0
    eng.reset()                                     # rebuild mid-life
    eng.generate([[7, 8, 9]], 4)
    after = snapshot_stats(eng.stats)
    stats_delta(before, after)                      # raises on regression
    assert after["prefix_lookups"] >= before["prefix_lookups"]
    assert after["preemptions"] >= before["preemptions"]
    assert after["evicted_blocks"] >= before["evicted_blocks"]


def test_engine_tap_skips_quiet_windows(tiny_model):
    model, params = tiny_model
    eng = LPUEngine(model, params, EngineConfig(slots=2, max_seq=64,
                                                paged=True,
                                                block_size=16))
    sink = RingBufferTracker(64)
    tap = EngineTap(eng)
    assert tap.emit(sink, t=0.0) is None            # nothing happened
    assert sink.seen == 0
    eng.generate([[1, 2, 3]], 4)
    rec = tap.emit(sink, t=1.0)
    # the first generated token comes out of prefill, the other three
    # out of decode steps: the delta mirrors the cumulative counter
    assert rec is not None and rec["delta"]["tokens"] == eng.stats.tokens
    assert math.isfinite(rec["dt_ms"])


# -- spans ---------------------------------------------------------------

SPAN_CONFIGS = {
    "fused": dict(),
    "host": dict(sampling="host"),
    "chunked": dict(prefill_chunk=8),
    "speculative": dict(speculate="ngram", draft_k=2),
}
PROMPTS = [[1, 2, 3, 1, 2, 3], [4, 5, 6, 7], [8, 9], [1, 2, 3, 1, 2]]


class CountingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: counts opens."""
    opened = 0

    def __init__(self, name, **counts):
        type(self).opened += 1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass

    def set_metadata(self, **counts):
        pass


def _ancestors(span):
    names = []
    while span.parent is not None:
        span = span.parent
        names.append(span.name)
    return names


def test_spans_off_record_nothing_and_open_no_annotation(tiny_model,
                                                         monkeypatch):
    model, params = tiny_model
    CountingAnnotation.opened = 0
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountingAnnotation)
    eng = LPUEngine(model, params, EngineConfig(slots=2, max_seq=64,
                                                paged=True, block_size=16))
    assert eng.spans is NO_SPANS and not NO_SPANS.on
    eng.generate(PROMPTS, 4)
    assert CountingAnnotation.opened == 0
    assert not hasattr(NO_SPANS, "spans") and vars(NO_SPANS) == {}
    # with no tracker and no budget the front end diffs no counters
    fe = AsyncFrontend(eng)
    assert fe._taps == [] and not fe.spans.on
    # an attached recorder opens one annotation per span (a request's
    # own spans are kept in memory only)
    rec = SpanRecorder().attach(eng)
    try:
        eng.generate(PROMPTS[:1], 2)
    finally:
        rec.detach()
    assert CountingAnnotation.opened == sum(
        not s.name.startswith("lpu.request.") for s in rec.spans) > 0
    assert eng.spans is NO_SPANS


def test_span_nesting_gives_parent_ids():
    t = iter(range(100))
    rec = SpanRecorder(clock=lambda: float(next(t)))
    with rec.span("a"):
        with rec.span("b", x=1) as b:
            with rec.span("c"):
                pass
            b.count(y=2)
        with rec.span("d"):
            pass
    with rec.span("e"):
        pass
    got = [(r["name"], r["id"], r["parent"], r["counts"])
           for r in rec.records()]
    assert got == [("a", 0, -1, {}), ("b", 1, 0, {"x": 1, "y": 2}),
                   ("c", 2, 1, {}), ("d", 3, 0, {}), ("e", 4, -1, {})]
    a, b, c = rec.spans[:3]
    assert a.start < b.start < c.start < c.end < b.end < a.end


def test_span_records_round_trip_through_jsonl(tmp_path):
    rec = SpanRecorder()
    with rec.span("lpu.step", ring=0):
        with rec.span("lpu.wait", bytes=64):
            pass
    rec.queued(7)
    rec.admitted(7)
    rec.request_done(7, rec.clock(), rec.clock())
    records = rec.records()
    path = str(tmp_path / "spans.jsonl")
    with JsonlTracker(path) as tr:
        for r in records:
            tr.log(r)
    back = read_jsonl(path)
    assert back == records
    assert [r["name"] for r in back] == [
        "lpu.step", "lpu.wait", "lpu.request.queue", "lpu.request.prefill",
        "lpu.request.decode"]
    assert back[1]["parent"] == back[0]["id"] and back[1]["counts"] == {
        "bytes": 64}
    assert all(r["rid"] == 7 for r in back[2:])
    with pytest.raises(ValueError):
        validate_record({**back[0], "end": back[0]["t"] - 1.0})


def test_gc_collection_is_a_span():
    import gc
    import threading
    import types
    rec = SpanRecorder().attach(types.SimpleNamespace())
    try:
        with rec.span("lpu.step") as step:
            gc.collect()
            # another thread's collection stops this one too: kept, but
            # not nested in this thread's spans
            other = threading.Thread(target=gc.collect)
            other.start()
            other.join(timeout=60)
            assert not other.is_alive()
        with rec.span("lpu.tick"):
            pass
    finally:
        rec.detach()
    assert rec._on_gc not in gc.callbacks
    full = [s for s in rec.spans
            if s.name == "py.gc" and s.counts["generation"] == 2]
    assert [s.parent for s in full] == [step, None]
    assert all("collected" in s.counts and s.end >= s.start for s in full)
    tick = [s for s in rec.spans if s.name == "lpu.tick"]
    assert tick and tick[0].parent is None          # the stack is intact
    n = len(rec.spans)
    gc.collect()                                    # detached: not kept
    assert len(rec.spans) == n


@pytest.mark.parametrize("kind", sorted(SPAN_CONFIGS))
def test_engine_spans_nest_and_count_steps(tiny_model, kind):
    model, params = tiny_model
    conf = EngineConfig(slots=2, max_seq=64, paged=True, block_size=16,
                        **SPAN_CONFIGS[kind])
    plain = LPUEngine(model, params, conf).generate(PROMPTS, 6)
    eng = LPUEngine(model, params, conf)
    rec = SpanRecorder().attach(eng)
    try:
        got = eng.generate(PROMPTS, 6)
    finally:
        rec.detach()
    assert got == plain                              # bit-identical streams
    spans = rec.spans
    names = {s.name for s in spans}
    assert {"lpu.step", "lpu.schedule", "lpu.prefill", "lpu.decode",
            "lpu.prepare", "lpu.dispatch", "lpu.wait",
            "lpu.reconcile"} <= names
    waits = [s for s in spans if s.name == "lpu.wait"
             and _ancestors(s)[:2] == ["lpu.decode", "lpu.step"]]
    assert waits
    decode = [s for s in spans if s.name == "lpu.decode"]
    assert sum(s.counts["steps"] for s in decode) == eng.stats.steps
    steps = [s for s in spans if s.name == "lpu.step"]
    assert sum(s.counts["steps"] for s in steps) == eng.stats.steps
    assert sum(s.counts["prefills"] for s in steps) == eng.stats.prefills
    assert all(s.counts["ring"] == 0 for s in steps)
    prefill = [s for s in spans if s.name == "lpu.prefill"]
    assert {s.counts["rid"] for s in prefill} == set(range(len(PROMPTS)))
    assert all(s.end >= s.start for s in spans)


def test_request_spans_carry_rid_and_front_end_stamps(tiny_model):
    model, params = tiny_model
    eng = LPUEngine(model, params, EngineConfig(slots=2, max_seq=64,
                                                paged=True, block_size=16))
    rec = SpanRecorder().attach(eng)
    sink = RingBufferTracker(100000)

    async def go():
        async with AsyncFrontend(eng, tracker=sink) as fe:
            streams = [fe.submit(p, 5) for p in PROMPTS]
            for s in streams:
                await s.drain()
        return streams

    try:
        streams = asyncio.run(go())
    finally:
        rec.detach()
    for st in streams:
        got = {s.name: s for s in rec.requests[st.rid]}
        assert set(got) == {"lpu.request.queue", "lpu.request.prefill",
                            "lpu.request.decode"}
        assert all(s.rid == st.rid for s in got.values())
        q, p, d = (got[f"lpu.request.{k}"]
                   for k in ("queue", "prefill", "decode"))
        assert q.end == p.start and p.end == d.start
        assert p.end == st.timeline.t_first         # the front end's stamp
        assert q.start >= st.timeline.t_submit
    kinds = [r for r in sink.records() if r["kind"] == "span"]
    assert len(kinds) == len(rec.spans)
    names = {r["name"] for r in kinds}
    assert {"lpu.tick", "lpu.submit", "lpu.step"} <= names
    ticks = [r for r in kinds if r["name"] == "lpu.tick"]
    assert sum(r["counts"]["finished"] for r in ticks) == len(PROMPTS)


def test_spans_land_in_the_profiler_trace(tiny_model, tmp_path):
    import glob

    from jax.profiler import ProfileData
    model, params = tiny_model
    eng = LPUEngine(model, params, EngineConfig(slots=2, max_seq=64,
                                                paged=True, block_size=16))
    eng.generate(PROMPTS[:2], 3)                     # compile outside
    rec = SpanRecorder().attach(eng)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.generate(PROMPTS[:2], 3)
    finally:
        jax.profiler.stop_trace()
        rec.detach()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    evs = [(e.name, dict(e.stats)) for pl in ProfileData.from_file(
        path).planes for ln in pl.lines for e in ln.events
        if e.name.startswith("lpu.")]
    kept = sorted(s.name for s in rec.spans if s.name.startswith("lpu.")
                  and not s.name.startswith("lpu.request."))
    assert sorted(n for n, _ in evs) == kept
    step = [st for n, st in evs if n == "lpu.step"]
    assert sum(st["steps"] for st in step) == sum(
        s.counts["steps"] for s in rec.spans if s.name == "lpu.step")


PROGRAMS = ("lpu_decode_window", "lpu_decode", "lpu_prefill", "lpu_chunk",
            "lpu_verify", "lpu_write_pages", "lpu_copy_block",
            "lpu_sample_first")


def test_engine_programs_carry_stable_names(tiny_model):
    import jax.numpy as jnp
    import numpy as np
    model, params = tiny_model
    eng = LPUEngine(model, params, EngineConfig(slots=2, max_seq=64,
                                                paged=True, block_size=16))
    i32 = jnp.int32
    tok = jnp.zeros((2, 1), i32)
    pos = jnp.zeros((2,), i32)
    tables = jnp.zeros((2, eng.table_len), i32)
    row, pc = jax.eval_shape(eng._prefill, params, jnp.zeros((1, 16), i32),
                             i32(3))
    carry, samp = eng._slot_state()
    f32 = jnp.float32
    lowered = {
        "lpu_decode_window": eng._window(1).lower(
            params, eng.cache, tables, *carry, eng.rng, *samp,
            np.bool_(False)),
        "lpu_decode": eng._decode.lower(params, eng.cache, tok, pos, tables),
        "lpu_prefill": eng._prefill.lower(params, jnp.zeros((1, 16), i32),
                                          i32(3)),
        "lpu_chunk": eng._prefill_chunk_fn.lower(
            params, eng.cache, jnp.zeros((1, 8), i32),
            jnp.zeros((eng.table_len,), i32), i32(0), i32(8)),
        "lpu_verify": eng._verify(1).lower(
            params, eng.cache, jnp.zeros((1, 4), i32),
            jnp.zeros((1, 4), i32), jnp.zeros((4, eng.table_len), i32),
            jnp.ones((4,), i32), jnp.zeros((2, 1), i32), eng.rng,
            jnp.zeros((2,), f32), pos, jnp.ones((2,), f32),
            jnp.ones((2,), bool)),
        "lpu_write_pages": eng._write_pages.lower(
            eng.cache, pc, jnp.zeros((1,), i32)),
        "lpu_copy_block": eng._copy_block.lower(eng.cache, i32(1), i32(2)),
        "lpu_sample_first": eng._sample_one.lower(
            jnp.zeros(row.shape, row.dtype), eng.rng, f32(0.0), i32(0),
            f32(1.0)),
    }
    assert sorted(lowered) == sorted(PROGRAMS)
    for name, low in lowered.items():
        assert low.as_text().startswith(f"module @jit_{name} "), name


def test_fleet_steps_carry_their_ring(tiny_model):
    model, params = tiny_model
    fleet = MultiRingEngine(model, params, None, rings=2,
                            config=EngineConfig(slots=2, max_seq=64,
                                                paged=True, block_size=16))
    rec = SpanRecorder().attach(fleet)
    try:
        fleet.generate(PROMPTS, 4)
    finally:
        rec.detach()
    steps = [s for s in rec.spans if s.name == "lpu.step"]
    assert {s.counts["ring"] for s in steps} == {0, 1}
    for ring, eng in enumerate(fleet.engines):
        assert eng.spans is NO_SPANS
        assert sum(s.counts["steps"] for s in steps
                   if s.counts["ring"] == ring) == eng.stats.steps
