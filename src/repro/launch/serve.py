"""Serving driver: load (or init) a model and run the LPU engine.

CLI (CPU-feasible defaults):
  PYTHONPATH=src python -m repro.launch.serve --arch smollm --reduced \
      --requests 8 --max-new 16

Ring parallelism: ``--tp N`` shards the model over an N-wide ESL ring
(weights AND the paged KV pool split 1/N per rank); ``--rings R`` serves
R independent sub-rings concurrently — ``tp * rings`` devices total,
one engine per sub-ring with least-loaded request routing (paper C2/C3).
On CPU the driver fakes the devices automatically
(``--xla_force_host_platform_device_count``), so
``--tp 2 --rings 2`` is runnable on a laptop.

Paged-KV knobs: ``--block-size`` (tokens per KV block), ``--num-blocks``
(pool size incl. the reserved null block; 0 = dense-equivalent capacity),
``--kv-budget-mb`` (size the pool from a per-rank HBM budget instead),
``--min-bucket`` (smallest power-of-two prefill bucket), ``--dense``
(force the contiguous per-slot cache), ``--paged-kernel
{auto,stream,gather}`` (stream KV tiles through the Pallas paged kernel
vs. materialize the contiguous gather view — see docs/serving.md).

Sampling / dispatch knobs: ``--sampling {fused,host}`` (fused = sample
inside the jitted decode program, only token ids reach the host — the
paper's on-chip "sampling with sort"; host = the synced baseline that
ships the full logits row per token), ``--steps-per-sync N`` (run N
decode steps per host readback via one lax.scan window), ``--block-s``
(override the planned KV stream tile / flash chunk for hardware tuning),
``--prefill-chunk C`` (chunked prefill: prompts become resident C tokens
per step, interleaved with decode windows, so a long prompt never stalls
in-flight streams — 0 = today's monolithic bucketed prefill),
``--prefix-cache {on,off}`` (prefix caching: a shared system prompt's
blocks are prefilled once and mapped — refcounted, copy-on-write — into
every later request's table; only the un-cached tail prefills),
``--speculate {off,ngram,model}`` + ``--draft-k K`` (speculative
decoding: a drafter proposes K tokens per slot, ONE chunk-as-batch
verify pass scores them against the pool, and rejection sampling
accepts a prefix — greedy streams bit-identical, stochastic streams
exactly target-distributed; ``model`` drafts with a reduced smollm-135m
running greedily at batch 1).

Plans take the mapper's serve dtypes: bf16 weights and compute.  The
persistent compilation cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``<repo>/.jax_cache``.

Precision knobs: ``--kv-dtype {auto,float16,bfloat16,float32,int8,fp8}``
(KV pool storage precision — int8/fp8 store per-(row, head) absmax
scales beside the pool and dequantize inside the paged kernel's tile
loop, halving the KV stream's HBM bytes) and ``--w-dtype {auto,int8}``
(streamed weight precision of the gemv chain).  See docs/serving.md
"KV & weight precision".

Front-end knobs (any of these routes the batch through the async
streaming frontend instead of the blocking generate loop):
``--affinity {least_loaded,prefix}`` (fleet routing: least-loaded vs
route-to-the-ring-whose-prefix-cache-owns-the-prompt),
``--budget-ms B`` (SLO scheduling: retune prefill_chunk /
steps_per_sync each step from a measured EWMA seeded by the analytic
step-time prior), ``--max-pending N`` (admission bound with structured
backpressure), ``--tracker PATH`` (jsonl telemetry: per-window
EngineStats deltas, per-request TTFT / ms-per-token records, and the
engine's spans, ``span`` records).  See
docs/serving.md "Async front end, SLO scheduling & telemetry".
"""
from __future__ import annotations

import argparse
import sys

from repro.launch.fake_devices import ensure_host_devices

ensure_host_devices(sys.argv)   # must precede the jax import

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compiler.mapper import plan_model  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.latency_model import LPU_FPGA, step_time_prior  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_serving_mesh  # noqa: E402
from repro.models.registry import build_model  # noqa: E402
from repro.serving.config import EngineConfig  # noqa: E402
from repro.serving.engine import LPUEngine, MultiRingEngine  # noqa: E402
from repro.serving.sampler import SamplingParams  # noqa: E402


def serve_async(engine, cfg, args, prompts, sp):
    """Drive the batch through the async streaming frontend instead of
    the blocking ``generate()`` loop — the path ``--budget-ms`` /
    ``--max-pending`` / ``--tracker`` select.  Backpressure rejections
    are retried after the fleet quiesces (a CLI batch has nowhere to
    shed load to), and the per-request TTFT / ms-per-token summary the
    frontend's timelines collect is printed at the end."""
    import asyncio

    from repro.serving.budget import BudgetScheduler
    from repro.serving.frontend import AdmissionRejected, AsyncFrontend
    from repro.serving.tracker import JsonlTracker, SpanRecorder

    budget = None
    if args.budget_ms > 0:
        prior = step_time_prior(cfg, max(args.tp, 1), LPU_FPGA,
                                kv_len=args.max_seq,
                                steps_per_sync=args.steps_per_sync)
        budget = BudgetScheduler(args.budget_ms, prior_step_s=prior,
                                 max_chunk=args.max_seq)
    tracker = JsonlTracker(args.tracker) if args.tracker else None
    spans = SpanRecorder().attach(engine) if tracker is not None else None
    retries = 0

    async def go():
        nonlocal retries
        async with AsyncFrontend(engine, budget=budget,
                                 tracker=tracker) as fe:
            streams = []
            for p in prompts:
                while True:
                    try:
                        streams.append(fe.submit(p, args.max_new, sp))
                        break
                    except AdmissionRejected:
                        retries += 1
                        await fe.join()     # backpressure: drain first
            outs = [await s.drain() for s in streams]
        return fe, streams, outs

    try:
        fe, streams, outs = asyncio.run(go())
    finally:
        if spans is not None:
            spans.detach()
    tl = [s.timeline for s in streams if s.timeline.t_first is not None]
    ttft = sorted(t.ttft_ms for t in tl)
    mpt = sorted(t.ms_per_token for t in tl if t.tokens >= 2)
    c = fe.counters
    print(f"[serve] frontend: {c['completed']} completed "
          f"{c['failed']} failed {c['cancelled']} cancelled "
          f"({c['rejected']} backpressure rejections, {retries} retried)")
    if ttft:
        print(f"[serve] ttft p50/max {ttft[len(ttft) // 2]:.1f}/"
              f"{ttft[-1]:.1f} ms"
              + (f", ms/token p50/max {mpt[len(mpt) // 2]:.2f}/"
                 f"{mpt[-1]:.2f}" if mpt else ""))
    if budget is not None:
        print(f"[serve] budget={args.budget_ms}ms: {len(budget.planned)} "
              f"plans, mu_step {budget.mu_step * 1e3:.3f} ms "
              f"({budget.observed_windows} windows, "
              f"{budget.observed_chunks} chunks observed)")
    if tracker is not None:
        print(f"[serve] tracker: {tracker.written} records "
              f"({len(spans.spans)} spans) -> {tracker.path}")
    return outs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--tp", type=int, default=1,
                    help="ESL ring width (devices per model replica)")
    ap.add_argument("--rings", type=int, default=1,
                    help="independent sub-rings (engines); uses "
                         "tp*rings devices")
    ap.add_argument("--no-overlap", action="store_true",
                    help="blocking collectives baseline (vs ESL overlap)")
    ap.add_argument("--dense", action="store_true",
                    help="force the dense per-slot KV cache")
    ap.add_argument("--block-size", type=int, default=0,
                    help="tokens per KV block (0 = min(128, max_seq))")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="KV pool size incl. null block "
                         "(0 = dense-equivalent capacity)")
    ap.add_argument("--kv-budget-mb", type=int, default=0,
                    help="per-rank KV HBM budget in MiB (sizes the pool "
                         "when --num-blocks is 0)")
    ap.add_argument("--min-bucket", type=int, default=16,
                    help="smallest power-of-two prefill bucket")
    ap.add_argument("--paged-kernel", default="auto",
                    choices=("auto", "stream", "gather"),
                    help="paged decode dataflow: stream KV tiles through "
                         "the Pallas kernel (no per-request copy), gather "
                         "the contiguous view (reference oracle), or auto")
    ap.add_argument("--sampling", default="fused",
                    choices=("fused", "host"),
                    help="fused: sample in-jit, only token ids reach the "
                         "host; host: per-token logits readback baseline")
    ap.add_argument("--steps-per-sync", type=int, default=1,
                    help="decode steps per host sync (fused sampling "
                         "only): N steps run as one lax.scan window")
    ap.add_argument("--block-s", type=int, default=0,
                    help="KV stream tile / flash chunk override threaded "
                         "to plan_block_s (0 = planned default)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: make prompts resident N "
                         "tokens per step, interleaved with decode "
                         "windows (paged only; 0 = monolithic bucketed "
                         "prefill)")
    ap.add_argument("--prefix-cache", default="off",
                    choices=("on", "off"),
                    help="prefix caching: admissions whose prompt hits "
                         "a cached block-aligned prefix map the shared "
                         "blocks (refcounted, copy-on-write) into their "
                         "table and prefill only the tail (paged only)")
    ap.add_argument("--speculate", default="off",
                    choices=("off", "ngram", "model"),
                    help="speculative decoding: draft k tokens per slot "
                         "(ngram: suffix-match over the visible stream; "
                         "model: a reduced smollm-135m drafter), verify "
                         "all of them in ONE chunk-as-batch pass and "
                         "accept a rejection-sampled prefix (paged only)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="draft tokens proposed per speculative round")
    ap.add_argument("--kv-dtype", default="auto",
                    choices=("auto", "float16", "bfloat16", "float32",
                             "int8", "fp8"),
                    help="KV pool storage precision: fp dtypes restore "
                         "the pool; int8/fp8 quantize per-(row, head) "
                         "with absmax scales dequantized in-kernel "
                         "(paged only; auto = the plan's cache dtype)")
    ap.add_argument("--w-dtype", default="auto",
                    choices=("auto", "int8"),
                    help="streamed weight precision of the gemv chain "
                         "(int8 with per-output-column scales)")
    ap.add_argument("--chaos", default="",
                    help="deterministic fault injection: comma-separated "
                         "kind@step[:ring] with kinds ring|stall|nan|"
                         "corrupt (e.g. 'ring@3,nan@7:1'); forces the "
                         "supervised fleet driver — see docs/serving.md "
                         "'Fault tolerance'")
    ap.add_argument("--max-migrations", type=int, default=3,
                    help="recompute-migrations per request before it "
                         "surfaces a structured failure")
    ap.add_argument("--heartbeat-timeout", type=float, default=30.0,
                    help="ring liveness timeout in (virtual, under "
                         "chaos) seconds before drain/rebuild")
    ap.add_argument("--affinity", default="least_loaded",
                    choices=("least_loaded", "prefix"),
                    help="fleet request routing: least-loaded, or "
                         "prefix-affinity (route to the ring whose "
                         "prefix cache owns the prompt's deepest "
                         "block-aligned prefix; needs --prefix-cache on)")
    ap.add_argument("--budget-ms", type=float, default=0.0,
                    help="per-step latency budget (SLO): the async "
                         "frontend retunes prefill_chunk / "
                         "steps_per_sync each step from a measured "
                         "EWMA seeded by the analytic step-time prior "
                         "(0 = off; forces the async frontend path)")
    ap.add_argument("--max-pending", type=int, default=0,
                    help="frontend admission bound: in-flight streams "
                         "above this are rejected with a structured "
                         "AdmissionRejected (0 = unbounded; forces the "
                         "async frontend path)")
    ap.add_argument("--tracker", default="",
                    help="write per-window EngineStats deltas, "
                         "per-request TTFT/ms-per-token records and the "
                         "engine's spans to this jsonl file (forces the "
                         "async frontend path)")
    args = ap.parse_args()

    print(f"[serve] compile cache: {use_compile_cache()}")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    tp, rings = args.tp, args.rings
    if tp > 1 or rings > 1:
        mesh = make_serving_mesh(tp=tp, rings=rings)
        plan = plan_model(cfg, ("model",), (tp,), "serve",
                          esl_overlap=not args.no_overlap, remat="none")
    else:
        mesh = None
        plan = plan_model(cfg, None, (1,), "serve", esl_overlap=False,
                          remat="none")
    model = build_model(cfg, plan)
    params, _ = model.init(jax.random.PRNGKey(0))
    draft_model = draft_params = None
    if args.speculate == "model":
        # the drafter is its own tiny model: always single-device (it
        # proposes on the host loop), reduced so it is cheap relative
        # to the target
        dcfg = get_config("smollm-135m").reduced()
        dplan = plan_model(dcfg, None, (1,), "serve", esl_overlap=False,
                           remat="none")
        draft_model = build_model(dcfg, dplan)
        draft_params, _ = draft_model.init(jax.random.PRNGKey(1))
    econf = EngineConfig(slots=args.slots, max_seq=args.max_seq,
                         paged=False if args.dense else None,
                         block_size=args.block_size,
                         num_blocks=args.num_blocks,
                         kv_budget_bytes=args.kv_budget_mb << 20,
                         min_bucket=args.min_bucket,
                         paged_kernel=args.paged_kernel,
                         sampling=args.sampling,
                         steps_per_sync=args.steps_per_sync,
                         block_s=args.block_s,
                         prefill_chunk=args.prefill_chunk,
                         prefix_cache=args.prefix_cache == "on",
                         speculate=args.speculate, draft_k=args.draft_k,
                         kv_dtype=args.kv_dtype, w_dtype=args.w_dtype,
                         chaos=args.chaos,
                         max_migrations=args.max_migrations,
                         heartbeat_timeout_s=args.heartbeat_timeout,
                         affinity=args.affinity,
                         budget_ms=args.budget_ms,
                         max_pending=args.max_pending)
    fleet = rings > 1 or bool(args.chaos)
    if fleet:
        # seed each ring's straggler monitor with the analytic latency
        # model's step-time prior (LPU-FPGA point) so outlier detection
        # is armed from the first measured step
        prior = step_time_prior(cfg, max(tp, 1), LPU_FPGA,
                                kv_len=args.max_seq,
                                steps_per_sync=args.steps_per_sync)
        if mesh is not None:
            engine = MultiRingEngine(model, params, mesh, ring_size=tp,
                                     config=econf, step_prior_s=prior,
                                     draft_model=draft_model,
                                     draft_params=draft_params)
        else:
            engine = MultiRingEngine(model, params, None,
                                     rings=max(rings, 1), config=econf,
                                     step_prior_s=prior,
                                     draft_model=draft_model,
                                     draft_params=draft_params)
        first = engine.engines[0]
    else:
        engine = LPUEngine(model, params, econf, mesh=mesh,
                           draft_model=draft_model,
                           draft_params=draft_params)
        first = engine

    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, cfg.vocab_size,
                                size=rng.randint(2, 10)))
               for _ in range(args.requests)]
    sp = SamplingParams(args.temperature, args.top_k, args.top_p)

    def cb(rid, tok):
        pass  # streaming hook (stdout spam suppressed)

    if args.budget_ms > 0 or args.max_pending > 0 or args.tracker:
        outs = serve_async(engine, cfg, args, prompts, sp)
    else:
        outs = engine.generate(prompts, max_new_tokens=args.max_new,
                               params=sp, stream_cb=cb)
    mode = f"paged/{first.paged_kernel}" if first.paged else "dense"
    if fleet:
        print(f"[serve] {len(outs)} requests over {engine.n_rings} "
              f"sub-rings (tp={tp} each), routed {engine.router.routed}")
        for i, (eng, st) in enumerate(zip(engine.engines,
                                          engine.per_ring_stats())):
            print(f"[serve]   ring{i}: {st.tokens} tokens, "
                  f"{st.tokens_per_s:.1f} tok/s, occ {st.occupancy:.2f}, "
                  f"kv/rank {eng.per_rank_kv_bytes()} B")
        fc = engine.fleet_counters()
        print(f"[serve] ft: chaos={args.chaos or 'off'} "
              f"ring_failures={fc['ring_failures']} "
              f"migrated={fc['migrated_requests']} "
              f"retries={fc['retries']} "
              f"rejected={fc['rejected_requests']} "
              f"failed={fc['failed_requests']} "
              f"events={fc['events']}")
    else:
        st = first.stats
        print(f"[serve] {len(outs)} requests, {st.tokens} tokens, "
              f"{st.tokens_per_s:.1f} tok/s, occupancy {st.occupancy:.2f}, "
              f"{st.steps} decode steps, tp={tp}")
        print(f"[serve] kv={mode} dtype={first.kv_dtype} "
              f"w_dtype={first.w_dtype} bytes={first.kv_cache_bytes()} "
              f"(per-rank {first.per_rank_kv_bytes()}, "
              f"dense-equiv {first.dense_equiv_bytes()}), "
              f"kv_moved/step={first.kv_bytes_moved_per_step()}, "
              f"prefill traces={st.prefill_traces}, "
              f"preemptions={st.preemptions}")
        print(f"[serve] sampling={first.sampling} "
              f"steps_per_sync={first.steps_per_sync}: "
              f"{st.host_syncs} host syncs "
              f"({st.syncs_per_token:.2f}/token), "
              f"{st.bytes_to_host_per_token:.1f} B->host/token, "
              f"overrun={st.overrun_tokens}, "
              f"block_s={first.decode_block_s()} "
              f"(planned {first.planned_block_s()})")
        print(f"[serve] prefill_chunk={first.prefill_chunk}: "
              f"{st.prefill_chunks} chunks, "
              f"decode_stalls={st.decode_stalls}")
        print(f"[serve] prefix_cache={args.prefix_cache}: "
              f"hit_rate={st.prefix_hit_rate:.2f} "
              f"({st.prefix_hits}/{st.prefix_lookups}), "
              f"hit_blocks={st.prefix_hit_blocks}, "
              f"prefill_tokens_saved={st.prefill_tokens_saved}, "
              f"cow={st.cow_blocks}, evicted={st.evicted_blocks}")
        print(f"[serve] speculate={first.speculate} "
              f"draft_k={first.draft_k}: {st.spec_rounds} rounds, "
              f"accepted {st.accepted_tokens}/{st.draft_tokens} drafts "
              f"(rate {st.acceptance_rate:.2f}, "
              f"{st.accepted_per_window:.2f}/window)")
    for i, o in enumerate(outs[:4]):
        print(f"  req{i}: {o[:12]}")


if __name__ == "__main__":
    main()
