"""Chip smoke run: serve OPT-1.3B at full width through the LPU engine.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # tp=4 ring + four one-chip replicas

One chip: builds ``opt-1.3b`` at published width in bf16 with weights from
``--seed`` through ``plan_model`` / ``build_model`` / ``LPUEngine`` (the
calls ``launch/serve.py`` makes), answers mixed-length requests through
``generate()`` and ``AsyncFrontend``, and holds the streamed paged
kernel to the gather oracle on the first decode step.  ``--chips 4``
runs only the multi-chip phase: the same model as a tp=4 ESL ring and as
four one-chip replicas behind ``RingRouter``, each against a one-chip
engine on the same prompts.

Every check that fails exits non-zero.  Without a TPU the script exits
non-zero before any phase.  The last line of standard output is one JSON
object naming the device the run used.
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "opt-1.3b"
BLOCK = 128
MAX_NEW = 32
# mixed prompt lengths: prefill buckets 128, 256 and 512
PROMPT_LENS = (5, 17, 40, 77, 128, 150, 230, 300, 411)
# stream-vs-oracle agreement on the first decode step: the largest logit
# difference, as a share of the largest reference logit.  bf16 keeps 8
# significand bits; the two dataflows round q and p differently at every
# one of the 24 layers.
LOGIT_TOL = 2.0 ** -4


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require_tpu(count: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is "
                 f"{devs[0].platform!r}); this check runs on the chip only")
    if len(devs) < count:
        sys.exit(f"chip_smoke: --chips {count} needs {count} TPU devices, "
                 f"found {len(devs)}")
    return devs


def make_prompts(cfg, seed: int, lens=PROMPT_LENS):
    import numpy as np
    rng = np.random.RandomState(seed)
    return [list(map(int, rng.randint(2, cfg.vocab_size, size=n)))
            for n in lens]


def build(cfg, seed: int, tp: int = 0):
    """``tp=0``: the mesh-free single-device plan; ``tp>=1``: a plan for a
    tp-wide ``model`` ring (``--tp``/``--rings`` in launch/serve.py)."""
    import jax
    from repro.compiler.mapper import plan_model
    from repro.models.registry import build_model
    if tp:
        plan = plan_model(cfg, ("model",), (tp,), "serve", esl_overlap=True,
                          remat="none")
    else:
        plan = plan_model(cfg, None, (1,), "serve", esl_overlap=False,
                          remat="none")
    model = build_model(cfg, plan)
    params, _ = model.init(jax.random.PRNGKey(seed))
    return model, params


def engine_config(slots: int, max_seq: int, **kw):
    from repro.serving.config import EngineConfig
    return EngineConfig(slots=slots, max_seq=max_seq, block_size=BLOCK, **kw)


def check_outputs(outs, prompts, vocab: int, what: str) -> int:
    if len(outs) != len(prompts):
        raise AssertionError(f"{what}: {len(outs)} answers for "
                             f"{len(prompts)} requests")
    for o in outs:
        if len(o) != MAX_NEW or not all(0 <= t < vocab for t in o):
            raise AssertionError(f"{what}: bad answer {o[:8]}... "
                                 f"(len {len(o)})")
    return sum(len(o) for o in outs)


def first_step_logits(eng, prompts):
    """Prefill ``prompts`` into ``eng``'s pool and run ONE decode step,
    through the engine's own jitted programs.  Returns the prefill rows
    and the decode-step logits over the real vocabulary (f32, host).
    Blocks are laid out by hand; the engine's scheduler is not used, so
    run this on an engine that serves nothing afterwards."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serving.kv_cache import blocks_for
    bs, V = eng.block_size, eng.cfg.vocab_size
    tables = np.zeros((eng.slots, eng.table_len), np.int32)
    toks = np.zeros((eng.slots, 1), np.int32)
    pos = np.zeros((eng.slots,), np.int32)
    rows, nxt = [], 1
    for b, p in enumerate(prompts):
        bucket = eng.sched.bucket(len(p))
        buf = np.zeros((1, bucket), np.int32)
        buf[0, :len(p)] = p
        row, pc = eng._prefill(eng.params, jnp.asarray(buf),
                               jnp.int32(len(p)))
        n = blocks_for(len(p) + 1, bs)       # room for the decoded token
        tables[b, :n] = np.arange(nxt, nxt + n)
        nxt += n
        eng.cache = eng._write_pages(eng.cache, pc,
                                     jnp.asarray(tables[b, :bucket // bs]))
        row = np.asarray(row, np.float32)[:V]
        rows.append(row)
        toks[b, 0] = int(row.argmax())
        pos[b] = len(p)
    logits, eng.cache = eng._decode(eng.params, eng.cache,
                                    jnp.asarray(toks), jnp.asarray(pos),
                                    jnp.asarray(tables))
    logits = np.asarray(logits, np.float32)[:len(prompts), :V]
    return np.stack(rows), logits


def agreement(ref, got, what: str) -> dict:
    """Largest logit difference against the reference, and greedy-token
    agreement on every row whose reference top-2 margin exceeds twice
    that difference (closer rows may legitimately flip)."""
    import numpy as np
    if not (np.isfinite(ref).all() and np.isfinite(got).all()):
        raise AssertionError(f"{what}: non-finite logits")
    diff = float(np.abs(got - ref).max())
    scale = float(np.abs(ref).max())
    top2 = np.sort(ref, -1)[:, -2:]
    margin = top2[:, 1] - top2[:, 0]
    sure = margin > 2 * diff
    same = ref.argmax(-1) == got.argmax(-1)
    out = dict(max_abs_diff=diff, max_abs_logit=scale,
               rel=diff / max(scale, 1e-30), tol=LOGIT_TOL,
               rows=int(len(ref)), rows_decided=int(sure.sum()),
               argmax_equal=int(same.sum()),
               argmax_equal_decided=int((same & sure).sum()))
    log(f"{what}: max|dlogit| {diff:.6g} / max|logit| {scale:.6g} = "
        f"{out['rel']:.6g} (tolerance {LOGIT_TOL:.6g}); greedy token "
        f"equal on {out['argmax_equal']}/{out['rows']} rows, "
        f"{out['argmax_equal_decided']}/{out['rows_decided']} of the rows "
        f"whose top-2 margin exceeds 2x that difference")
    if out["rel"] > LOGIT_TOL:
        raise AssertionError(f"{what}: logits differ beyond tolerance")
    if out["argmax_equal_decided"] != out["rows_decided"]:
        raise AssertionError(f"{what}: a decided greedy token differs")
    return out


def run_frontend(engine, prompts):
    from repro.serving.frontend import AsyncFrontend

    async def go():
        async with AsyncFrontend(engine) as fe:
            streams = [fe.submit(p, MAX_NEW) for p in prompts]
            return [await s.drain() for s in streams]
    return asyncio.run(go())


def memory_line(devs) -> str:
    parts = []
    for d in devs:
        st = d.memory_stats() or {}
        parts.append(f"{d.id}:{st.get('bytes_in_use', 'n/a')}")
    return "bytes_in_use per device " + " ".join(parts)


def phase_one_chip(cfg, seed: int, devs) -> None:
    import jax
    from repro.serving.engine import LPUEngine
    model, params = build(cfg, seed)
    plan = model.plan
    n_params = sum(x.size for x in jax.tree.leaves(params))
    prompts = make_prompts(cfg, seed)

    # -- serve: 16 slots x 512 tokens.  The decode window holds four copies
    # of the pool (argument, result and two loop buffers: nothing is
    # donated), so 16 x 1024 would not leave the weights room on 16 GB --
    eng = LPUEngine(model, params, engine_config(16, 512))
    if eng.paged_kernel != "stream":
        raise AssertionError(f"paged_kernel resolved to {eng.paged_kernel}")
    log(f"arch={cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads} d_head={cfg.d_head} vocab={cfg.vocab_size} "
        f"params={n_params}")
    log(f"dtype weights={plan.param_dtype} compute={plan.compute_dtype} "
        f"kv={eng.kv_dtype}; paged_kernel={eng.paged_kernel}; "
        f"pool {eng.num_blocks} blocks x {eng.block_size} tokens = "
        f"{eng.kv_cache_bytes()} B")
    t0 = time.perf_counter()
    outs = eng.generate(prompts, max_new_tokens=MAX_NEW)
    cold = time.perf_counter() - t0
    n_tok = check_outputs(outs, prompts, cfg.vocab_size, "generate")
    t0 = time.perf_counter()
    again = eng.generate(prompts, max_new_tokens=MAX_NEW)
    warm = time.perf_counter() - t0
    if again != outs:
        raise AssertionError("greedy generate() is not reproducible")
    log(f"generate(): {len(prompts)} requests answered "
        f"(prompt lengths {list(PROMPT_LENS)}), {n_tok} tokens generated")
    log(f"seconds: first call {cold:.3f} (compiles included), second call "
        f"{warm:.3f} (steady state); compile ~{cold - warm:.3f}; "
        f"{n_tok / warm:.1f} tokens/s steady")
    fe_prompts = prompts[:3]
    fe_outs = run_frontend(eng, fe_prompts)
    check_outputs(fe_outs, fe_prompts, cfg.vocab_size, "AsyncFrontend")
    if fe_outs != outs[:3]:
        raise AssertionError("AsyncFrontend answers differ from generate()")
    log(f"AsyncFrontend: {len(fe_outs)} requests answered, identical to "
        "generate()")
    del eng
    gc.collect()

    # -- the streamed kernel against the gather oracle, same engine config
    res = {}
    for kernel in ("gather", "stream"):
        e = LPUEngine(model, params,
                      engine_config(8, 512, paged_kernel=kernel))
        res[kernel] = (e.generate(prompts[:8], max_new_tokens=MAX_NEW),
                       first_step_logits(e, prompts[:8]))
        del e
        gc.collect()
    (g_out, (g_rows, g_logits)) = res["gather"]
    (s_out, (s_rows, s_logits)) = res["stream"]
    if (g_rows != s_rows).any():
        raise AssertionError("prefill rows differ; they share one program")
    agreement(g_logits, s_logits, "stream vs gather, first decode step")
    same = sum(a == b for a, b in zip(g_out, s_out))
    log(f"stream vs gather generate(): {same}/{len(g_out)} requests with "
        f"identical {MAX_NEW}-token greedy streams")
    peak = (devs[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"peak_bytes_in_use {peak}")


def phase_four_chips(cfg, seed: int, devs) -> None:
    from repro.launch.mesh import make_serving_mesh
    from repro.serving.engine import LPUEngine, MultiRingEngine
    prompts = make_prompts(cfg, seed)[:8]
    conf = engine_config(8, 512)

    model, params = build(cfg, seed)
    ref_eng = LPUEngine(model, params, conf)
    ref_out = ref_eng.generate(prompts, max_new_tokens=MAX_NEW)
    check_outputs(ref_out, prompts, cfg.vocab_size, "one-chip engine")
    ref_rows, ref_logits = first_step_logits(ref_eng, prompts)
    del ref_eng, params, model
    gc.collect()
    log(f"one-chip reference: {len(ref_out)} requests answered")

    # -- tp=4 ESL ring: weights and the KV pool sharded over four chips
    model, params = build(cfg, seed, tp=4)
    ring = LPUEngine(model, params, conf, mesh=make_serving_mesh(tp=4))
    del params
    gc.collect()
    if ring.paged_kernel != "stream":
        raise AssertionError(f"ring paged_kernel is {ring.paged_kernel}")
    t0 = time.perf_counter()
    ring_out = ring.generate(prompts, max_new_tokens=MAX_NEW)
    cold = time.perf_counter() - t0
    n_tok = check_outputs(ring_out, prompts, cfg.vocab_size, "tp=4 ring")
    log(f"tp=4 ring: {len(ring_out)} requests, {n_tok} tokens in "
        f"{cold:.3f} s (compiles included); per-rank KV "
        f"{ring.per_rank_kv_bytes()} B of {ring.kv_cache_bytes()} B")
    log("tp=4 ring " + memory_line(devs[:4]))
    _, ring_logits = first_step_logits(ring, prompts)
    agreement(ref_logits, ring_logits, "tp=4 ring vs one chip, first "
              "decode step")
    same = sum(a == b for a, b in zip(ref_out, ring_out))
    log(f"tp=4 ring vs one chip generate(): {same}/{len(ref_out)} requests "
        f"with identical {MAX_NEW}-token greedy streams")
    del ring, model
    gc.collect()

    # -- four one-chip replicas behind RingRouter.  Each replica runs the
    # one-device ring program (the tp=1 plan ``--rings`` serves), so its
    # reference is that program on one chip; against the mesh-free engine
    # it is a different program and is held to the logit tolerance only
    model, params = build(cfg, seed, tp=1)
    one = LPUEngine(model, params, conf, mesh=make_serving_mesh(tp=1))
    one_out = one.generate(prompts, max_new_tokens=MAX_NEW)
    _, one_logits = first_step_logits(one, prompts)
    del one
    gc.collect()
    agreement(ref_logits, one_logits, "one-device ring vs mesh-free one "
              "chip, first decode step")
    fleet = MultiRingEngine(model, params, make_serving_mesh(tp=1, rings=4),
                            ring_size=1, config=conf)
    del params
    gc.collect()
    t0 = time.perf_counter()
    rep_out = fleet.generate(prompts, max_new_tokens=MAX_NEW)
    cold = time.perf_counter() - t0
    check_outputs(rep_out, prompts, cfg.vocab_size, "replicas")
    routed = fleet.router.routed
    failures = [e.detail for e in fleet.events if e.kind == "ring_failed"]
    log(f"4 replicas: {len(rep_out)} requests in {cold:.3f} s (compiles "
        f"included), routed {routed}, ring failures {failures}")
    log("4 replicas " + memory_line(devs[:4]))
    same = sum(a == b for a, b in zip(ref_out, rep_out))
    log(f"4 replicas vs mesh-free one chip: {same}/{len(ref_out)} requests "
        f"with identical {MAX_NEW}-token greedy streams")
    same = sum(a == b for a, b in zip(one_out, rep_out))
    log(f"4 replicas vs one-device ring: {same}/{len(one_out)} requests "
        "identical")
    if failures:
        raise AssertionError("the fleet drained a ring on a clean run")
    if rep_out != one_out:
        raise AssertionError("replica answers differ from the one-device "
                             "ring engine")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip serving phase; 4: only the tp=4 "
                         "ring and replica phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    devs = require_tpu(args.chips)
    import jax
    t_start = time.perf_counter()
    log(f"devices: {len(devs)} x {devs[0].device_kind}; compile cache "
        f"{cache_dir}")
    cfg = get_config(ARCH)
    if args.chips == 4:
        phase_four_chips(cfg, args.seed, devs)
    else:
        phase_one_chip(cfg, args.seed, devs)
    log(f"all phases passed in {time.perf_counter() - t_start:.3f} s")
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}))


if __name__ == "__main__":
    main()
