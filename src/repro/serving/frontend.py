"""Async streaming front end over :class:`LPUEngine`/:class:`MultiRingEngine`.

The blocking ``submit/step/drain`` API measures throughput; users feel
per-request latency.  This module is the seam between the two: an
asyncio front end that

* streams tokens out as each decode window reconciles —
  :meth:`AsyncFrontend.submit` returns a :class:`TokenStream` (an async
  iterator) fed by the engine's ``stream_cb`` the moment the host sees
  each token;
* bounds admission — at most ``max_pending`` streams in flight, beyond
  which ``submit`` raises a structured :class:`AdmissionRejected`
  (backpressure belongs at the edge, not as an unbounded queue inside
  the scheduler);
* supports cancellation that actually frees resources —
  :meth:`TokenStream.cancel` releases the request's slot and pool
  blocks between steps (`LPUEngine.cancel`), so an abandoned stream
  never holds KV;
* drives SLO scheduling — with a :class:`repro.serving.budget.
  BudgetScheduler` attached, every pump tick re-plans ``prefill_chunk``
  and ``steps_per_sync`` from the measured-step-time EWMA before
  stepping the engine;
* emits telemetry — an optional :class:`repro.serving.tracker.Tracker`
  receives per-window ``EngineStats`` deltas (snapshot-and-diff via
  :class:`EngineTap`) and a per-request TTFT / ms-per-token record at
  each stream's end; with a :class:`repro.serving.tracker.SpanRecorder`
  attached to the engine, the pump's ticks and submits are spans too,
  and every recorded span goes to the tracker when the front end
  stops.

Concurrency model: ONE event loop, no threads.  The pump task calls the
engine's synchronous ``step()`` directly and yields
(``await asyncio.sleep(0)``) between steps, so consumers drain their
queues exactly at window-reconcile granularity.  That keeps the token
streams bit-identical to the blocking path (greedy — it is the same
engine stepping in the same order; tests/test_frontend.py locks this)
and makes cancellation race-free by construction: every frontend entry
point runs between engine steps.
"""
from __future__ import annotations

import asyncio
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence

from repro.serving.budget import BudgetScheduler
from repro.serving.engine import LPUEngine, MultiRingEngine, Request
from repro.serving.sampler import SamplingParams
from repro.serving.tracker import (EngineTap, NullTracker, RequestTimeline,
                                   Tracker)


class AdmissionRejected(RuntimeError):
    """Structured backpressure signal: the frontend's in-flight window
    is full.  Carries the numbers a client needs to back off sensibly
    instead of parsing a message string."""

    def __init__(self, pending: int, limit: int):
        self.pending = pending
        self.limit = limit
        super().__init__(
            f"admission rejected: {pending} streams in flight >= "
            f"max_pending={limit}")


class TokenStream:
    """One request's async token stream.

    ``async for tok in stream`` yields generated token ids as the
    engine reconciles them; iteration ends when the request completes,
    fails, or is cancelled — ``status`` / ``error`` say which.  The
    accumulated tokens are also kept in ``tokens`` (bit-identical to
    the blocking path's ``results[rid]``).
    """

    def __init__(self, rid: int, frontend: "AsyncFrontend",
                 timeline: RequestTimeline):
        self.rid = rid
        self.tokens: List[int] = []
        self.status = "streaming"     # -> completed | failed | cancelled
        self.error: Optional[str] = None
        self.timeline = timeline
        self._pending: Deque[int] = deque()
        self._event = asyncio.Event()
        self._frontend = frontend

    @property
    def done(self) -> bool:
        return self.status != "streaming"

    def _push(self, tok: int) -> None:
        self.tokens.append(tok)
        self._pending.append(tok)
        self._event.set()

    def _finish(self, status: str, error: Optional[str] = None) -> None:
        self.status = status
        self.error = error
        self._event.set()

    def __aiter__(self) -> "TokenStream":
        return self

    async def __anext__(self) -> int:
        while True:
            if self._pending:
                return self._pending.popleft()
            if self.done:
                raise StopAsyncIteration
            self._event.clear()
            await self._event.wait()

    async def drain(self) -> List[int]:
        """Consume the stream to the end; returns all tokens."""
        async for _ in self:
            pass
        return self.tokens

    async def cancel(self) -> bool:
        """Abort this stream and free its engine resources.  True if
        the cancellation landed (False: already finished)."""
        return self._frontend.cancel(self.rid)


class AsyncFrontend:
    """Async serving facade over one engine or a multi-ring fleet.

    Use as an async context manager::

        async with AsyncFrontend(engine, max_pending=64) as fe:
            stream = fe.submit(prompt, max_new_tokens=32)
            async for tok in stream: ...

    ``counters`` tracks the admission ledger; at any quiesced point
    ``completed + failed + cancelled == submitted`` (and ``rejected``
    counts submits that never reached the engine).
    """

    def __init__(self, engine, *, max_pending: Optional[int] = None,
                 budget: Optional[BudgetScheduler] = None,
                 tracker: Optional[Tracker] = None,
                 clock=time.perf_counter):
        self.engine = engine
        self.engines: List[LPUEngine] = (
            list(engine.engines) if isinstance(engine, MultiRingEngine)
            else [engine])
        cfg = self.engines[0].config
        self.max_pending = (cfg.max_pending if max_pending is None
                            else int(max_pending))
        if budget is None and cfg.budget_ms > 0:
            budget = BudgetScheduler(cfg.budget_ms)
        self.budget = budget
        self.tracker = tracker if tracker is not None else NullTracker()
        self.clock = clock
        # per-tick EngineStats deltas feed the tracker and the budget
        # scheduler; with neither there is nothing to diff for
        watched = budget is not None or \
            not isinstance(self.tracker, NullTracker)
        self._taps = [EngineTap(e, ring=i)
                      for i, e in enumerate(self.engines)] if watched else []
        self._streams: Dict[int, TokenStream] = {}
        self._inflight: Dict[int, TokenStream] = {}
        self.counters = dict(submitted=0, completed=0, failed=0,
                             cancelled=0, rejected=0)
        self._wake = asyncio.Event()
        self._idle = asyncio.Event()
        self._idle.set()
        self._closing = False
        self._task: Optional[asyncio.Task] = None

    # -- lifecycle -----------------------------------------------------

    async def __aenter__(self) -> "AsyncFrontend":
        await self.start()
        return self

    async def __aexit__(self, *exc) -> None:
        await self.stop()

    async def start(self) -> None:
        if self._task is None:
            self._closing = False
            self._task = asyncio.create_task(self._pump())

    @property
    def spans(self):
        """The engine's span recorder (``NO_SPANS`` unless attached)."""
        return self.engines[0].spans

    async def stop(self) -> None:
        """Finish in-flight work, stop the pump, write the recorded
        spans and flush the tracker."""
        self._closing = True
        self._wake.set()
        if self._task is not None:
            await self._task
            self._task = None
        if self.spans.on:
            for rec in self.spans.records():
                self.tracker.log(rec)
        self.tracker.finish()

    async def join(self) -> None:
        """Wait until every in-flight stream has ended."""
        await self._idle.wait()

    # -- submission / cancellation ------------------------------------

    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               params: Optional[SamplingParams] = None,
               tenant: Optional[str] = None) -> TokenStream:
        """Admit one request; returns its :class:`TokenStream`.

        Raises :class:`AdmissionRejected` when ``max_pending`` streams
        are already in flight — backpressure, not queueing.
        """
        if self.max_pending and len(self._inflight) >= self.max_pending:
            self.counters["rejected"] += 1
            self.tracker.log({"kind": "event", "t": self.clock(),
                              "name": "admission_rejected",
                              "pending": len(self._inflight),
                              "limit": self.max_pending})
            raise AdmissionRejected(len(self._inflight), self.max_pending)
        t0 = self.clock()
        with self.spans.span("lpu.submit") as sp:
            rid = self.engine.submit(list(prompt), max_new_tokens, params,
                                     stream_cb=self._on_token)
            sp.count(rid=rid)
        stream = TokenStream(rid, self, RequestTimeline(rid, t0,
                                                        tenant=tenant))
        self._streams[rid] = stream
        self._inflight[rid] = stream
        self.counters["submitted"] += 1
        self._idle.clear()
        self._wake.set()
        return stream

    def cancel(self, rid: int) -> bool:
        """Abort one in-flight stream; frees its slot and pool blocks.
        False if the request already finished (nothing to do)."""
        stream = self._inflight.get(rid)
        if stream is None:
            return False
        req = self.engine.cancel(rid)
        if req is None:
            # not in the engine anymore: it finished inside the current
            # pump tick and will be finalized when step() returns
            return False
        self._finalize(rid, "cancelled")
        return True

    # -- the pump ------------------------------------------------------

    def _on_token(self, rid: int, tok: int) -> None:
        stream = self._streams.get(rid)
        if stream is None:            # e.g. blocking-path co-tenant
            return
        stream.timeline.on_token(self.clock())
        stream._push(tok)

    def _finalize(self, rid: int, status: str,
                  error: Optional[str] = None) -> None:
        stream = self._inflight.pop(rid, None)
        if stream is None:
            return
        self.counters[status] += 1
        stream._finish(status, error)
        t = self.clock()
        self.spans.request_done(rid, stream.timeline.t_first, t)
        self.tracker.log(stream.timeline.record(status, t))
        if not self._inflight:
            self._idle.set()

    def _has_work(self) -> bool:
        return self.engine.has_work()

    def _apply_budget(self) -> None:
        for eng in self.engines:
            chunk, steps = self.budget.plan(
                chunked=eng.prefill_chunk > 0,
                fused=eng.sampling == "fused")
            eng.set_step_knobs(prefill_chunk=chunk, steps_per_sync=steps)

    def _observe_budget(self, dt_s: float, deltas: List[Dict[str, int]]
                        ) -> None:
        steps = sum(d.get("steps", 0) for d in deltas)
        chunks = sum(d.get("prefill_chunks", 0) for d in deltas)
        tokens = sum(d.get("prefill_chunks", 0) * e.prefill_chunk
                     for d, e in zip(deltas, self.engines))
        if steps and not chunks:
            self.budget.observe_window(dt_s, steps)
        elif chunks and not steps:
            self.budget.observe_chunk(dt_s, tokens)
        elif steps and chunks:
            # mixed tick (interleaved admission runs a prefill chunk AND
            # a decode window in the same step): split the measured wall
            # between the phases in proportion to the model's current
            # predictions.  A fully interleaved workload would otherwise
            # never train the EWMA — every tick mixed, every tick
            # skipped — and self-consistent splitting still converges:
            # whichever phase the model underestimates absorbs a larger
            # share of the residual on the next update.
            pred_w = self.budget.mu_step * steps
            pred_c = self.budget.mu_tok * max(tokens, 1)
            total = pred_w + pred_c
            if total > 0:
                self.budget.observe_window(dt_s * pred_w / total, steps)
                self.budget.observe_chunk(dt_s * pred_c / total,
                                          max(tokens, 1))

    def _tick(self) -> None:
        """One engine step with SLO planning + telemetry around it."""
        with self.spans.span("lpu.tick") as sp:
            if self.budget is not None:
                self._apply_budget()
            t0 = self.clock()
            done = self.engine.step()
            dt = self.clock() - t0
            deltas = []
            for tap in self._taps:
                before = dict(tap._prev)
                rec = tap.emit(self.tracker, self.clock(), dt_ms=dt * 1e3)
                deltas.append(rec["delta"] if rec is not None else
                              {k: 0 for k in before})
            if self.budget is not None:
                self._observe_budget(dt, deltas)
            finished = 0
            for req in done:
                if req.rid not in self._inflight:
                    continue
                finished += 1
                if req.failed:
                    self._finalize(req.rid, "failed", req.error)
                else:
                    self._finalize(req.rid, "completed")
            sp.count(finished=finished)

    async def _pump(self) -> None:
        while True:
            if not self._has_work():
                if self._closing:
                    return
                self._wake.clear()
                # re-check: a submit may have landed before clear()
                if self._has_work() or self._closing:
                    continue
                await self._wake.wait()
                continue
            self._tick()
            # yield so consumers drain at window granularity
            await asyncio.sleep(0)


async def serve_trace(frontend: AsyncFrontend, trace,
                      speed: float = 1.0) -> List[TokenStream]:
    """Replay a :mod:`benchmarks.traces` trace against a frontend:
    submit each request at ``arrival_s / speed`` (wall), collect every
    stream, and wait for the fleet to quiesce.  Rejected submits are
    recorded as ``None`` placeholders so callers can count them."""
    loop = asyncio.get_event_loop()
    t0 = loop.time()
    streams: List[Optional[TokenStream]] = []
    for req in trace:
        delay = req.arrival_s / speed - (loop.time() - t0)
        if delay > 0:
            await asyncio.sleep(delay)
        try:
            streams.append(frontend.submit(
                req.prompt, req.max_new_tokens, tenant=req.tenant))
        except AdmissionRejected:
            streams.append(None)
    await frontend.join()
    return streams
