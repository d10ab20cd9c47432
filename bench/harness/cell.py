"""One run of one cell: build, warm up, drive the window, drain, measure,
check.

The timed path is the serving program as its CLI builds it
(``plan_model`` -> ``build_model`` -> ``LPUEngine``), driven
through ``AsyncFrontend`` by the cell's traffic.  The benchmark makes
the weights from the seed (the configuration's family module,
``harness/families/<family>.py``), measures on the host clock with
tracing off, and in a ``--trace 1`` run reads the per-layer metrics
from its own records and the profiler's trace.  After the window it
frees the program and holds a sample of the served tokens to the
family's plain float32 reference.
"""
from __future__ import annotations

import asyncio
import gc
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from harness import traffic as tr
from harness.families import seed_key
from harness.spec import Cell, load_peaks, metric_reader

MIN_SERVED_TOKENS = 400        # served tokens the correctness sample holds
MAX_SAMPLE_SEQS = 8            # ...in at most this many requests
FAILED_MS = 1e7                # a failed request's latency: beyond any tail
DRAIN_TIMEOUT_S = 120.0        # how long requests due in the window may take
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


@dataclass
class ReqRec:
    req: tr.Req
    t_due: float                   # due (open) or sent (closed), host clock
    t_submit: float = math.nan     # submit() returned
    stream: object = None          # the TokenStream while the window runs
    status: str = "pending"
    served: List[int] = field(default_factory=list)
    t_first: Optional[float] = None
    t_last: Optional[float] = None

    def settle(self) -> None:
        """Keep what the metrics need and drop the stream (it holds the
        front end, and through it the engine's device memory)."""
        if self.stream is not None:
            tl = self.stream.timeline
            self.served = list(self.stream.tokens)
            self.t_first, self.t_last = tl.t_first, tl.t_last
        self.stream = None

    def ttft_ms(self) -> float:
        if self.status != "completed" or self.t_first is None:
            return FAILED_MS
        return (self.t_first - self.t_due) * 1e3

    def tpot_ms(self) -> Optional[float]:
        if self.status != "completed":
            return FAILED_MS
        if len(self.served) < 2:
            return None
        return (self.t_last - self.t_first) * 1e3 / (len(self.served) - 1)


@dataclass
class StepRec:
    t0: float
    t1: float
    prefills: int
    steps: int                     # decode device steps reconciled
    ctx: List[int]                 # resident tokens of live slots, before


@dataclass
class Run:
    """What a run hands the metric readers."""
    cell: Cell
    dims: Dict[str, int]
    block: int
    seconds: float
    reqs: List[ReqRec]
    steps: List[StepRec]
    token_times: List[float]
    t0: float
    t_close: float
    stats0: Dict[str, int]
    stats1: Dict[str, int]
    peaks: Dict[str, float] = field(default_factory=dict)
    trace: object = None           # harness.trace.Reduced in traced runs

    @property
    def loop(self) -> str:
        return self.cell.traffic["loop"]

    @property
    def family(self):
        """The configuration's family module: the readers' operation
        and byte counts and the decode kernel's name."""
        return self.cell.family

    def window_steps(self) -> List[StepRec]:
        return [s for s in self.steps if s.t0 >= self.t0
                and s.t1 <= self.t_close]

    def decode_ctx(self) -> List[List[int]]:
        """Resident lengths of the live slots at each decode device step
        of the window."""
        out = []
        for s in self.window_steps():
            for j in range(s.steps):
                out.append([p + j for p in s.ctx])
        return out


def pctl(xs: List[float], q: float) -> float:
    """The ``q``-quantile (0..1), linear between order statistics."""
    return float(np.percentile(np.asarray(xs, np.float64), q * 100.0))


# -- set-up ----------------------------------------------------------------

def build(cell: Cell, seed: int, on_chip: bool):
    """The configuration's model, its weights (one jitted call from the
    seed, on the device, in the served dtype) and the engine, on one
    chip, all as the configuration's family says."""
    import jax
    from repro.compiler.mapper import plan_model
    from repro.models.registry import build_model
    from repro.serving.config import EngineConfig
    from repro.serving.engine import LPUEngine

    conf, fam = cell.config, cell.family
    d = fam.dims(conf["model"])
    cfg = fam.arch_config(conf, d)
    plan = plan_model(cfg, None, (1,), "serve", esl_overlap=False,
                      remat="none")
    model = build_model(cfg, plan)
    template = jax.eval_shape(lambda k: model.init(k)[0],
                              jax.random.PRNGKey(0))
    dtype = template["embed"].dtype
    params = jax.jit(lambda key: fam.program_params(
        fam.canonical(key, d, dtype), d, template))(seed_key(seed))
    e = conf["engine"]
    engine = LPUEngine(model, params,
                       EngineConfig(slots=e["slots"], max_seq=e["max_seq"],
                                    block_size=e["block_size"]))
    if on_chip and engine.paged_kernel != "stream":
        raise RuntimeError(f"paged kernel resolved to {engine.paged_kernel}, "
                           "not the streamed Pallas kernel")
    return engine, d


def warm_up(engine, traffic: dict) -> int:
    """Compile every program the window will run: each prefill bucket
    the traffic's prompt lengths reach, the decode window and the
    first-token sampler.  Returns the number of warm-up requests."""
    lo = int(traffic["prompt"].get("min", traffic["prompt"].get("value")))
    hi = tr.max_prompt(traffic)
    by_bucket: Dict[int, int] = {}
    for n in range(lo, hi + 1):
        by_bucket[engine.sched.bucket(n)] = n
    prompts = [[2 + (i % 97) for i in range(n)] for n in by_bucket.values()]
    engine.generate(prompts, max_new_tokens=2)
    return len(prompts)


def stats_dict(engine) -> Dict[str, int]:
    s = engine.stats
    return dict(steps=s.steps, tokens=s.tokens, prefills=s.prefills,
                busy_slot_steps=s.busy_slot_steps, slot_steps=s.slot_steps,
                preemptions=s.preemptions)


# -- the window ------------------------------------------------------------

class Driver:
    """Drives one window of the cell's traffic through ``AsyncFrontend``
    and records everything the metrics need."""

    def __init__(self, engine, traffic: dict, seed: int, seconds: float,
                 annotate: bool):
        self.engine = engine
        self.traffic = traffic
        self.seed = seed
        self.seconds = seconds
        self.annotate = annotate
        self.reqs: List[ReqRec] = []
        self.steps: List[StepRec] = []
        self.token_times: List[float] = []
        self.clock = time.perf_counter
        self.t0 = self.t_close = math.nan
        self.stats0: Dict[str, int] = {}
        self.stats1: Dict[str, int] = {}
        self.on_close = None           # called at the close, between steps

    def _span(self, name: str):
        if not self.annotate:
            import contextlib
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _wrap(self, fe) -> None:
        eng, step = self.engine, self.engine.step
        clock = self.clock

        def timed_step():
            st = eng.stats
            p0, s0 = st.prefills, st.steps
            ctx = [q.pos for q in eng.sched.active
                   if q is not None and not q.prefilling]
            t0 = clock()
            with self._span("bench.engine_step"):
                out = step()
            self.steps.append(StepRec(t0, clock(), st.prefills - p0,
                                      st.steps - s0, ctx))
            return out

        eng.step = timed_step
        on_token = fe._on_token

        def counted(rid, tok):
            self.token_times.append(clock())
            with self._span("bench.on_token"):
                on_token(rid, tok)

        fe._on_token = counted

    def _submit(self, fe, rec: ReqRec) -> None:
        from repro.serving.frontend import AdmissionRejected
        with self._span("bench.submit"):
            try:
                rec.stream = fe.submit(rec.req.prompt, rec.req.max_new)
            except AdmissionRejected:
                rec.status = "rejected"
        rec.t_submit = self.clock()

    async def _open(self, fe, end: float) -> None:
        for r in tr.open_schedule(self.traffic, self.seed, self.seconds):
            due = self.t0 + r.due_s
            if due >= end:
                break
            wait = due - self.clock()
            if wait > 0:
                await asyncio.sleep(wait)
            rec = ReqRec(r, due)
            self.reqs.append(rec)
            self._submit(fe, rec)

    async def _client(self, fe, stream: List[tr.Req], end: float) -> None:
        for r in stream:
            t = self.clock()
            if t >= end:
                return
            rec = ReqRec(r, t)
            self.reqs.append(rec)
            self._submit(fe, rec)
            if rec.stream is None:
                return
            await rec.stream.drain()

    async def _close(self, end: float) -> None:
        await asyncio.sleep(max(0.0, end - self.clock()))
        with self._span("bench.window_close"):
            self.t_close = self.clock()
        self.stats1 = stats_dict(self.engine)
        if self.on_close is not None:
            self.on_close()

    async def drive(self) -> None:
        from repro.serving.frontend import AsyncFrontend
        fe = AsyncFrontend(self.engine)
        self._wrap(fe)
        await fe.start()
        self.stats0 = stats_dict(self.engine)
        with self._span("bench.window_open"):
            self.t0 = self.clock()
        end = self.t0 + self.seconds
        closer = asyncio.create_task(self._close(end))
        if self.traffic["loop"] == "open":
            gens = [asyncio.create_task(self._open(fe, end))]
        else:
            per = tr.per_client_bound(self.traffic, self.seconds)
            gens = [asyncio.create_task(self._client(fe, s, end))
                    for s in tr.closed_streams(self.traffic, self.seed, per)]
        try:
            await closer
            await asyncio.wait_for(asyncio.gather(*gens), DRAIN_TIMEOUT_S)
            await asyncio.wait_for(fe.join(), DRAIN_TIMEOUT_S)
        except asyncio.TimeoutError:
            pass
        for g in gens:
            g.cancel()
        for rec in self.reqs:
            if rec.stream is None:
                continue
            if not rec.stream.done:
                fe.cancel(rec.stream.rid)
                rec.status = "timed_out"
            else:
                rec.status = rec.stream.status
        await fe.stop()
        for rec in self.reqs:
            rec.settle()


# -- metrics ---------------------------------------------------------------

def end_to_end(run: Run, names: List[str]) -> Dict[str, float]:
    """The window's end-to-end metrics (all but ``setup_s``).  A name's
    part before its first dot says what it measures: ``tpot_p90_ms.b1``
    is ``tpot_p90_ms`` under a bound of its own."""
    out: Dict[str, float] = {}
    reqs = run.reqs
    for name in names:
        base = name.split(".")[0]
        if base == "tokens_per_s":
            n = sum(1 for t in run.token_times
                    if run.t0 <= t < run.t0 + run.seconds)
            out[name] = n / run.seconds
        elif base == "ttft_p90_ms":
            out[name] = pctl([r.ttft_ms() for r in reqs], 0.9)
        elif base == "tpot_p90_ms":
            xs = [r.tpot_ms() for r in reqs]
            out[name] = pctl([x for x in xs if x is not None], 0.9)
        elif base != "setup_s":
            raise ValueError(f"no end-to-end metric {name!r}")
    return out


def log_stalls(run: Run) -> None:
    """How the window's ``step()`` calls and the host time between them
    spread: a host stall shows as a few long calls or gaps."""
    steps = run.window_steps()
    if len(steps) < 2:
        return
    ms = np.asarray([(s.t1 - s.t0) * 1e3 for s in steps if not s.prefills])
    gaps = np.asarray([(b.t0 - a.t1) * 1e3 for a, b in zip(steps, steps[1:])])
    if not ms.size:
        return
    med = float(np.median(ms))
    long = ms > 2 * med
    log(f"decode-only steps in the window: {len(ms)} calls of "
        f"{len(steps)}, median {med:.3f} ms, p99 "
        f"{pctl(list(ms), 0.99):.3f}, max {ms.max():.3f}; {int(long.sum())} "
        f"over twice the median, {float((ms[long] - med).sum()):.1f} ms "
        f"beyond it; host gaps between calls median "
        f"{float(np.median(gaps)):.3f} ms, max {gaps.max():.3f}, sum "
        f"{gaps.sum():.1f} ms")


# -- correctness -----------------------------------------------------------

def sample(run: Run, seed: int) -> List[ReqRec]:
    """Completed requests drawn from the seed: the longest served one
    first, then others until the sample holds enough served tokens."""
    done = [r for r in run.reqs if r.status == "completed" and r.served]
    if not done:
        return []
    rng = np.random.Generator(np.random.PCG64(seed + 7919))
    longest = max(done, key=lambda r: (len(r.served), -r.req.idx))
    rest = [r for r in done if r is not longest]
    rest = [rest[i] for i in rng.permutation(len(rest))]
    out, n = [longest], len(longest.served)
    for r in rest:
        if n >= MIN_SERVED_TOKENS or len(out) >= MAX_SAMPLE_SEQS:
            break
        out.append(r)
        n += len(r.served)
    return out


def expected_tokens(rec: ReqRec, max_seq: int) -> int:
    """Tokens the engine serves a request: its budget, or fewer where
    the sequence reaches ``max_seq - 1`` positions."""
    return min(rec.req.max_new, max_seq - len(rec.req.prompt))


def check(cell: Cell, run: Run, seed: int, d: Dict[str, int],
          control: bool = False) -> Dict[str, dict]:
    """The numbers ``correct`` is decided by, each beside its limit.

    With ``control``, the control takes the program's place in
    ``served_logit_gap`` (the gap of the token it puts first, at the
    same positions), so a sound control comes out not correct; the
    program's own gap is then ``program_logit_gap``, under the same
    limit."""
    import jax
    max_seq = cell.config["engine"]["max_seq"]
    picked = sample(run, seed)
    checks: Dict[str, dict] = {}
    bad_len = sum(1 for r in run.reqs if r.status == "completed"
                  and len(r.served) != expected_tokens(r, max_seq))
    bad_id = sum(1 for r in run.reqs for t in r.served
                 if not 0 <= t < d["V"])
    checks["requests_with_wrong_length"] = {"value": bad_len, "limit": 0}
    checks["tokens_out_of_vocab"] = {"value": bad_id, "limit": 0}
    checks["sampled_requests"] = {"value": len(picked), "limit": 1,
                                  "at_least": True}
    if picked:
        fam = cell.family
        c = jax.jit(lambda k: fam.canonical(k, d))(seed_key(seed))
        seqs = [(r.req.prompt, r.served) for r in picked]
        g, gc_ = fam.served_gaps(c, d, seqs, control=control)
        del c
        checks["served_tokens_compared"] = {"value": int(g.size),
                                            "limit": 1, "at_least": True}
        limit = float(cell.limit["served_logit_gap"])
        if gc_ is not None:
            checks["program_logit_gap"] = {"value": float(g.max()),
                                           "limit": limit}
            g = gc_
        checks["served_logit_gap"] = {"value": float(g.max()),
                                      "limit": limit}
    return checks


def passed(checks: Dict[str, dict]) -> bool:
    ok = True
    for v in checks.values():
        if v.get("at_least"):
            ok &= v["value"] >= v["limit"]
        else:
            ok &= v["value"] <= v["limit"]
    return bool(ok)


# -- one run ---------------------------------------------------------------

def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peaks = []
    for dv in devs:
        st = dv.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": max(peaks)}


def require_chips(chips: int) -> None:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform is {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, on_chip: bool = True,
             control: bool = False) -> dict:
    """One run; returns the result line (a dict).  With ``control``,
    the control stands in the program's place in the comparison
    (:func:`check`)."""
    import jax
    if on_chip:
        require_chips(cell.chips)
    engine, d = build(cell, seed, on_chip)
    n_warm = warm_up(engine, cell.traffic)
    # as a long-running server does once it has loaded: move what set-up
    # made out of the collector's way, so that a full collection in the
    # window walks the window's garbage only (without this, one ~0.17 s
    # gen-2 pause every few seconds stalls the host loop)
    gc.collect()
    gc.freeze()
    block = engine.block_size
    drv = Driver(engine, cell.traffic, seed, seconds, annotate=trace)
    compiles: List[str] = []
    listening = [False]

    def on_compile(name, secs, **kw):
        if listening[0] and name in (TRACE_EVENT, COMPILE_EVENT):
            compiles.append((name, kw.get("fun_name", "?"), secs))

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None

    def stop_listening():
        listening[0] = False
        if trace:
            jax.profiler.stop_trace()

    drv.on_close = stop_listening
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s ({n_warm} warm-up requests); window "
        f"{seconds} s")
    listening[0] = True
    asyncio.run(drv.drive())
    traced = [(f, round(t, 6)) for n, f, t in compiles if n == TRACE_EVENT]
    built = [(f, round(t, 6)) for n, f, t in compiles if n == COMPILE_EVENT]
    log(f"compiles in the window: {len(built)} backend compiles {built} "
        f"(a warm window has none); {len(traced)} jit-trace events "
        f"{traced} (seconds each; a few tens of microseconds is JAX's "
        "dispatch finding the program already traced)")
    device = device_info(cell.chips)
    run = Run(cell=cell, dims=d, block=block, seconds=seconds, reqs=drv.reqs, steps=drv.steps,
              token_times=drv.token_times, t0=drv.t0, t_close=drv.t_close,
              stats0=drv.stats0, stats1=drv.stats1)
    failed = sum(1 for r in run.reqs if r.status != "completed")
    metrics: Dict[str, dict] = {}
    extra: Dict[str, object] = {}
    if trace:
        from harness import trace as trace_mod
        run.peaks = load_peaks(device["kind"]) if on_chip else {}
        evs = trace_mod.load_xplane(log_dir)
        lo, hi = trace_mod.window_bounds(evs, "bench.window_open",
                                         "bench.window_close")
        run.trace = trace_mod.reduce(evs, lo, hi, cell.chips)
        shutil.rmtree(log_dir, ignore_errors=True)
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        extra["breakdown"] = run.trace.breakdown
        for m in cell.per_layer:
            v = metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = end_to_end(run, [m["name"] for m in cell.end_to_end])
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        for k, v in e2e.items():
            metrics[k] = {"value": v, "unit": units[k]}
        metrics["setup_s"] = {"value": setup_s, "unit": units["setup_s"]}
    log(f"window: {len(run.reqs)} requests due, {failed} failed; "
        f"{len(run.token_times)} tokens in all")
    log_stalls(run)
    # the program's state goes before the reference runs on the chip
    for x in jax.tree.leaves((engine.params, engine.cache)):
        x.delete()
    del engine, drv
    gc.unfreeze()
    gc.collect()
    checks = check(cell, run, seed, d, control=control)
    checks["failed_requests"] = {"value": failed, "limit": 0}
    ok = passed(checks)
    for k, v in checks.items():
        log(f"compared {k}: {v['value']} limit {v['limit']}")
    result = {"correct": ok, "attempted": len(run.reqs), "failed": failed,
              "metrics": metrics, "device": device}
    result.update(extra)
    result["checks"] = {k: [v["value"], v["limit"]] for k, v in checks.items()}
    return result
