"""Pluggable serving telemetry: the ``Tracker`` protocol and sinks.

Every layer of the serving stack — the async frontend, the SLO budget
scheduler, the fleet supervisor — emits structured time-series records
through ONE seam instead of printing banners.  The protocol is
levanter-style: a tracker is anything with ``log(record)`` / ``finish()``;
implementations here are deliberately boring (jsonl file, in-memory ring
buffer) so tests and CI can consume the stream without a metrics
backend.

Record schema (``validate_record``): every record carries

* ``kind``  — ``"engine_window"`` | ``"request"`` | ``"event"`` |
  ``"span"``
* ``t``     — seconds, caller-supplied monotonic clock

plus per-kind required fields:

* ``engine_window`` — ``ring``, ``step``, ``dt_ms`` and a ``delta`` dict
  of **non-negative** EngineStats counter deltas since the previous
  window (see below)
* ``request`` — ``rid``, ``status`` (completed|failed|cancelled|
  rejected), ``tokens``, ``ttft_ms``, ``ms_per_token``
* ``event`` — ``name`` plus free-form detail
* ``span`` — ``id``, ``name``, ``end`` (>= ``t``, its start),
  ``parent`` (the enclosing span's ``id``, -1 at the top) and
  ``counts`` (numbers counted where the span's work happened), plus
  ``rid`` on a request's spans (see :class:`SpanRecorder`)

EngineStats delta accounting
----------------------------
``EngineStats`` counters are cumulative for the life of the engine —
*including* across ``reset()``/ring rebuilds (the engine banks subsystem
counter bases at reset, so assigned fields like ``preemptions`` and the
prefix counters never regress).  The tracker seam therefore works by
snapshot-and-diff: :class:`EngineTap` keeps the previous snapshot and
emits only the per-window delta, and because the cumulative stream is
monotone every delta is ``>= 0`` and the deltas sum back to the final
cumulative counters (tests/test_tracker.py locks both properties, with
a migration in the middle).
"""
from __future__ import annotations

import gc
import json
import threading
import time
from collections import deque
from dataclasses import fields as dc_fields
from typing import Any, Callable, Deque, Dict, List, Optional

# EngineStats fields excluded from delta accounting: gauges (high-water
# marks are not flows) and wall (float accumulation, tracked as dt_ms on
# the window record itself).
GAUGE_FIELDS = frozenset({"peak_pool_blocks", "wall"})

KINDS = ("engine_window", "request", "event", "span")
REQUEST_STATUSES = ("completed", "failed", "cancelled", "rejected")
_REQUIRED = {
    "engine_window": ("ring", "step", "dt_ms", "delta"),
    "request": ("rid", "status", "tokens", "ttft_ms", "ms_per_token"),
    "event": ("name",),
    "span": ("id", "name", "end", "parent", "counts"),
}


def validate_record(rec: Dict[str, Any]) -> None:
    """Raise ``ValueError`` unless ``rec`` is schema-valid (see module
    docstring).  The jsonl sink validates on write so a malformed
    record fails the producer, never a downstream dashboard."""
    if not isinstance(rec, dict):
        raise ValueError(f"record must be a dict, got {type(rec).__name__}")
    kind = rec.get("kind")
    if kind not in KINDS:
        raise ValueError(f"record kind={kind!r} not in {KINDS}")
    t = rec.get("t")
    if not isinstance(t, (int, float)) or t != t:  # NaN guard
        raise ValueError(f"record t={t!r} must be a finite number")
    missing = [k for k in _REQUIRED[kind] if k not in rec]
    if missing:
        raise ValueError(f"{kind} record missing fields {missing}")
    if kind == "engine_window":
        delta = rec["delta"]
        if not isinstance(delta, dict):
            raise ValueError("engine_window delta must be a dict")
        neg = {k: v for k, v in delta.items() if v < 0}
        if neg:
            raise ValueError(
                f"engine_window delta went negative: {neg} — cumulative "
                "EngineStats regressed (reset() base accounting broken?)")
        if rec["dt_ms"] < 0:
            raise ValueError(f"dt_ms={rec['dt_ms']} must be >= 0")
    elif kind == "request":
        if rec["status"] not in REQUEST_STATUSES:
            raise ValueError(f"request status={rec['status']!r} not in "
                             f"{REQUEST_STATUSES}")
        if rec["tokens"] < 0:
            raise ValueError(f"tokens={rec['tokens']} must be >= 0")
    elif kind == "span":
        if not rec["end"] >= t:
            raise ValueError(f"span {rec['name']!r} ends at {rec['end']!r}, "
                             f"before its start {t}")
        if not isinstance(rec["counts"], dict):
            raise ValueError("span counts must be a dict")


class Tracker:
    """Protocol base: ``log`` one record, ``finish`` flushes/closes.

    Subclass and override; the base is a null sink so a tracker-less
    frontend can unconditionally call through it.
    """

    def log(self, rec: Dict[str, Any]) -> None:  # pragma: no cover
        pass

    def finish(self) -> None:
        pass

    # context-manager sugar so ``with JsonlTracker(p) as tr:`` closes
    def __enter__(self) -> "Tracker":
        return self

    def __exit__(self, *exc) -> None:
        self.finish()


class NullTracker(Tracker):
    """Explicit no-op sink (the default when telemetry is off)."""


class JsonlTracker(Tracker):
    """One schema-validated JSON object per line, append-only.

    The file format CI's tail-latency-smoke leg uploads as an artifact;
    ``read_jsonl`` round-trips it.
    """

    def __init__(self, path: str):
        self.path = str(path)
        self._fh = open(self.path, "w", encoding="utf-8")
        self.written = 0

    def log(self, rec: Dict[str, Any]) -> None:
        validate_record(rec)
        self._fh.write(json.dumps(rec, sort_keys=True) + "\n")
        self.written += 1

    def finish(self) -> None:
        if not self._fh.closed:
            self._fh.flush()
            self._fh.close()


def read_jsonl(path: str) -> List[Dict[str, Any]]:
    """Load and re-validate a jsonl tracker file (tests + CI gate)."""
    out: List[Dict[str, Any]] = []
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            validate_record(rec)
            out.append(rec)
    return out


class RingBufferTracker(Tracker):
    """Keep the last ``capacity`` records in memory — the live-dashboard
    sink (a serve banner or test asserts over a bounded recent window,
    never an unbounded history)."""

    def __init__(self, capacity: int = 1024):
        if capacity < 1:
            raise ValueError(f"capacity={capacity} must be >= 1")
        self.capacity = capacity
        self._buf: Deque[Dict[str, Any]] = deque(maxlen=capacity)
        self.seen = 0                  # total logged, incl. overwritten

    def log(self, rec: Dict[str, Any]) -> None:
        validate_record(rec)
        self._buf.append(rec)
        self.seen += 1

    def records(self) -> List[Dict[str, Any]]:
        return list(self._buf)

    def window(self, n: int) -> List[Dict[str, Any]]:
        """The most recent ``min(n, len)`` records, oldest first."""
        if n < 0:
            raise ValueError(f"n={n} must be >= 0")
        buf = list(self._buf)
        return buf[len(buf) - min(n, len(buf)):]


# ---------------------------------------------------------------------------
# EngineStats snapshot / delta (the tracker seam)
# ---------------------------------------------------------------------------

def counter_fields(stats) -> List[str]:
    """The monotone counter fields of an ``EngineStats`` (everything but
    the gauges) — derived from the dataclass so a new counter is picked
    up by telemetry without touching this module."""
    return [f.name for f in dc_fields(stats) if f.name not in GAUGE_FIELDS]


def snapshot_stats(stats) -> Dict[str, int]:
    """Copy the cumulative counters out of an ``EngineStats``."""
    return {k: getattr(stats, k) for k in counter_fields(stats)}


def stats_delta(prev: Dict[str, int], cur: Dict[str, int]) -> Dict[str, int]:
    """Per-window counter flow between two snapshots.  Raises if any
    counter regressed — cumulative EngineStats are monotone by contract
    (the engine banks subsystem bases across ``reset()``), so a negative
    delta is a bug upstream, never something to clamp away silently."""
    d = {k: cur[k] - prev.get(k, 0) for k in cur}
    neg = {k: v for k, v in d.items() if v < 0}
    if neg:
        raise ValueError(f"EngineStats counters regressed: {neg}")
    return d


class EngineTap:
    """Snapshot-and-diff adapter from one engine's ``EngineStats`` to
    ``engine_window`` records.  Quiet windows (all-zero delta) are
    skipped so an idle fleet does not flood the sink."""

    def __init__(self, engine, ring: int = 0):
        self.engine = engine
        self.ring = ring
        self._prev = snapshot_stats(engine.stats)
        self.windows = 0

    def emit(self, tracker: Tracker, t: float,
             dt_ms: float = 0.0) -> Optional[Dict[str, Any]]:
        cur = snapshot_stats(self.engine.stats)
        delta = stats_delta(self._prev, cur)
        self._prev = cur
        if not any(delta.values()):
            return None
        rec = {"kind": "engine_window", "t": float(t), "ring": self.ring,
               "step": self.engine.stats.steps,
               "dt_ms": float(max(dt_ms, 0.0)), "delta": delta}
        tracker.log(rec)
        self.windows += 1
        return rec


class RequestTimeline:
    """Per-request latency timestamps: submit, first token (TTFT), every
    token (ms/token), terminal status.  The frontend owns one per
    stream and emits a ``request`` record at the end."""

    def __init__(self, rid: int, t_submit: float, tenant: Optional[str] = None):
        self.rid = rid
        self.tenant = tenant
        self.t_submit = t_submit
        self.t_first: Optional[float] = None
        self.t_last: Optional[float] = None
        self.tokens = 0

    def on_token(self, t: float) -> None:
        if self.t_first is None:
            self.t_first = t
        self.t_last = t
        self.tokens += 1

    @property
    def ttft_ms(self) -> float:
        if self.t_first is None:
            return float("nan")
        return (self.t_first - self.t_submit) * 1e3

    @property
    def ms_per_token(self) -> float:
        """Mean inter-token latency over the decode phase (excludes
        TTFT; a 0/1-token stream has no decode phase -> 0)."""
        if self.tokens < 2 or self.t_first is None or self.t_last is None:
            return 0.0
        return (self.t_last - self.t_first) * 1e3 / (self.tokens - 1)

    def record(self, status: str, t: float) -> Dict[str, Any]:
        rec = {"kind": "request", "t": float(t), "rid": self.rid,
               "status": status, "tokens": self.tokens,
               "ttft_ms": (self.ttft_ms if self.t_first is not None
                           else -1.0),
               "ms_per_token": self.ms_per_token}
        if self.tenant is not None:
            rec["tenant"] = self.tenant
        return rec


# ---------------------------------------------------------------------------
# Spans: where the host's time goes inside the serving program
# ---------------------------------------------------------------------------

class _NoSpan:
    """The span an unrecorded engine opens: does nothing at all."""
    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def count(self, **counts) -> None:
        pass


NO_SPAN = _NoSpan()


class NoSpans:
    """The span recorder of an engine nobody records: every span is the
    shared :data:`NO_SPAN`, so a span costs one method call — no clock
    read, no profiler annotation, nothing kept."""
    on = False

    def span(self, name: str, **counts) -> _NoSpan:
        return NO_SPAN

    def queued(self, rid: int) -> None:
        pass

    def admitted(self, rid: int) -> None:
        pass

    def request_done(self, rid: int, t_first: Optional[float],
                     t_end: float) -> None:
        pass


NO_SPANS = NoSpans()


class Span:
    """One span: ``name`` from ``start`` to ``end`` on the recorder's
    clock, inside span ``parent`` (None at the top), with the ``counts``
    of the work done in it.  Use as a context manager; :meth:`count`
    adds counts known only once the work is done."""
    __slots__ = ("rec", "name", "start", "end", "parent", "rid", "counts",
                 "_ann")

    def __init__(self, rec: "SpanRecorder", name: str,
                 rid: Optional[int] = None, counts: Optional[dict] = None):
        self.rec = rec
        self.name = name
        self.rid = rid
        self.counts = counts or {}
        self.start = self.end = float("nan")
        self.parent: Optional[Span] = None
        self._ann = None

    def __enter__(self) -> "Span":
        rec = self.rec
        self._begin(rec._open[-1] if rec._open else None)
        rec._open.append(self)
        return self

    def __exit__(self, *exc) -> None:
        self._end(*exc)
        self.rec._open.pop()

    def _begin(self, parent: Optional["Span"]) -> None:
        self.parent = parent
        self.rec.spans.append(self)
        self._ann = self.rec._annotation(self.name, **self.counts)
        self._ann.__enter__()
        self.start = self.rec.clock()

    def _end(self, *exc) -> None:
        self.end = self.rec.clock()
        self._ann.__exit__(*exc)
        self._ann = None

    def count(self, **counts) -> None:
        self.counts.update(counts)
        if self._ann is not None:
            self._ann.set_metadata(**counts)

    def record(self, idx: int, parent: int) -> Dict[str, Any]:
        rec = {"kind": "span", "t": self.start, "id": idx,
               "name": self.name, "end": self.end, "parent": parent,
               "counts": dict(self.counts)}
        if self.rid is not None:
            rec["rid"] = self.rid
        return rec


class SpanRecorder(NoSpans):
    """Spans and counts at the serving program's layer boundaries.

    Attach it to an engine or a fleet (:meth:`attach`); the engine, its
    front end and the garbage collector then open named spans (listed
    in ``docs/serving.md``, "Spans"):

    * each span opens ``jax.profiler.TraceAnnotation(name, **counts)``,
      so that while a profiler trace is collected the span lands in it
      on the device trace's clock, its counts as event stats;
    * each is also kept in memory (:attr:`spans`), with its parent,
      until :meth:`records` hands them out as ``span`` records — the
      front end writes them through its :class:`Tracker` when it stops.

    A request's own spans are kept in memory only, under its rid
    (:attr:`requests`): ``lpu.request.queue`` from submit to admission
    (a preempted request queued again opens another),
    ``lpu.request.prefill`` from admission to its first token and
    ``lpu.request.decode`` from there to its end.  The first-token and
    end stamps are the front end's, so its clock must be this one.

    Nothing is dropped while attached: a long-running server detaches
    (or reads :meth:`records` and builds a new recorder) to bound it.
    """
    on = True

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        from jax.profiler import TraceAnnotation
        self.clock = clock
        self._annotation = TraceAnnotation
        self.spans: List[Span] = []
        self.requests: Dict[int, List[Span]] = {}
        self._open: List[Span] = []
        self._queued: Dict[int, float] = {}
        self._admitted: Dict[int, float] = {}
        self._engines: List[Any] = []
        self._gc: Optional[Span] = None

    # -- wiring ----------------------------------------------------------

    def attach(self, engine) -> "SpanRecorder":
        """Record ``engine`` (an ``LPUEngine`` or every ring of a
        ``MultiRingEngine``) and the garbage collector's pauses."""
        for eng in getattr(engine, "engines", [engine]):
            eng.spans = self
            self._engines.append(eng)
        self._thread = threading.get_ident()
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)
        return self

    def detach(self) -> None:
        """Stop recording: the engines go back to :data:`NO_SPANS`."""
        for eng in self._engines:
            eng.spans = NO_SPANS
        self._engines = []
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        # a collection holds the interpreter, whichever thread runs it;
        # it nests in the serving thread's open span only when that
        # thread runs it, and never joins the span stack
        if phase == "start":
            self._gc = Span(self, "py.gc",
                            counts={"generation": info["generation"]})
            mine = threading.get_ident() == self._thread
            self._gc._begin(self._open[-1] if mine and self._open else None)
        elif self._gc is not None:
            self._gc.count(collected=info["collected"])
            self._gc._end(None, None, None)
            self._gc = None

    # -- spans -----------------------------------------------------------

    def span(self, name: str, **counts) -> Span:
        return Span(self, name, counts=counts)

    def _request_span(self, name: str, rid: int, start: float,
                      end: float) -> None:
        sp = Span(self, name, rid)
        sp.start, sp.end = start, end
        self.spans.append(sp)
        self.requests.setdefault(rid, []).append(sp)

    def queued(self, rid: int) -> None:
        """Request ``rid`` entered the engine's queue (submit, or a
        preemption sent it back)."""
        self._queued[rid] = self.clock()

    def admitted(self, rid: int) -> None:
        """Request ``rid`` left the queue for a slot."""
        t = self.clock()
        t0 = self._queued.pop(rid, None)
        if t0 is not None:
            self._request_span("lpu.request.queue", rid, t0, t)
        self._admitted.setdefault(rid, t)

    def request_done(self, rid: int, t_first: Optional[float],
                     t_end: float) -> None:
        """Request ``rid`` ended at ``t_end``, its first token at
        ``t_first`` (None: it had none), both on the front end's
        clock."""
        t_adm = self._admitted.pop(rid, None)
        self._queued.pop(rid, None)
        if t_first is None:
            return
        if t_adm is not None:
            self._request_span("lpu.request.prefill", rid, t_adm, t_first)
        self._request_span("lpu.request.decode", rid, t_first, t_end)

    def records(self) -> List[Dict[str, Any]]:
        """Every closed span as a ``span`` record, in opening order; a
        record's ``id`` is its span's index in :attr:`spans`, its
        ``parent`` the enclosing span's (-1 at the top)."""
        ids = {id(sp): i for i, sp in enumerate(self.spans)}
        return [sp.record(i, ids[id(sp.parent)] if sp.parent else -1)
                for i, sp in enumerate(self.spans) if sp.end == sp.end]
