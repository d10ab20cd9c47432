"""Chunked prefill (--prefill-chunk): parity + interleave guarantees.

The engine's chunked-prefill mode replaces the monolithic bucketed
prefill with fixed-size chunks interleaved with decode windows.  These
tests pin the two contract halves:

* **Parity** — token streams are bit-identical to monolithic prefill
  for greedy decoding, for any chunk size (sub-block, block-aligned,
  block-crossing), under multi-step fused windows and under recompute
  preemption (incl. preemption of a partially-prefilled prompt).
* **Interleave** — while a long prompt trickles in chunk by chunk,
  every in-flight decode stream keeps producing a token per step and
  ``EngineStats.decode_stalls`` stays zero (the monolithic baseline
  stalls at least once on the same trace).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compiler.mapper import plan_model
from repro.configs import get_config
from repro.models.registry import build_model
from repro.serving.engine import LPUEngine

VOCAB = 512     # smollm reduced()


def _prompts(seed, lengths):
    """Seeded random prompts chosen for ROBUST greedy margins.

    Bit-identity assertions compare argmaxes, and XLA CPU's thread-
    count-dependent GEMM blocking jitters logits in the last few bits —
    a trace whose top-2 logit gap ever gets razor-thin (the repo-wide
    `[[1,2,3,...]]` trace has a 2.8e-4 step) flakes under load.  These
    seeds were picked so every sampled step of every test keeps a top-2
    margin >= 5e-3, ~50x the observed jitter.
    """
    rng = np.random.RandomState(seed)
    return [list(map(int, rng.randint(1, VOCAB, size=n)))
            for n in lengths]


# 4 short prompts + one 39-token long prompt (multi-chunk for every
# chunk size under test)
PROMPTS = _prompts(11, (7, 5, 3, 4, 39))


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("smollm-135m").reduced()
    plan = plan_model(cfg, None, (1,), "serve", esl_overlap=False,
                      remat="none", compute_dtype="float32",
                      param_dtype="float32")
    model = build_model(cfg, plan)
    params, _ = model.init(jax.random.PRNGKey(0))
    return model, params


@pytest.fixture(scope="module")
def mono_ref(tiny_model):
    """Monolithic-prefill reference streams on the shared trace."""
    model, params = tiny_model
    return LPUEngine(model, params, slots=3, max_seq=64, paged=True,
                     block_size=16).generate(PROMPTS, max_new_tokens=8)


# ---------------------------------------------------------------------------
# parity: chunked == monolithic, across chunk sizes and dispatch modes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [4, 16, 40])
def test_chunked_matches_monolithic(tiny_model, mono_ref, chunk):
    """Bit-identical greedy streams for a sub-block chunk (4 < block 16),
    a chunk-boundary == block-boundary chunk (16) and a chunk that
    crosses block boundaries mid-chunk (40); the trace includes a
    39-token prompt so every size exercises multi-chunk resume."""
    model, params = tiny_model
    eng = LPUEngine(model, params, slots=3, max_seq=64, paged=True,
                    block_size=16, prefill_chunk=chunk)
    assert eng.generate(PROMPTS, max_new_tokens=8) == mono_ref
    assert eng.stats.decode_stalls == 0
    assert eng.stats.prefill_chunks > len(PROMPTS) or chunk >= 40
    # ONE chunk trace regardless of the prompt-length mix
    assert eng.stats.prefill_traces == 1


def test_chunked_parity_gather_and_host_sampling(tiny_model, mono_ref):
    """The chunk program honors the engine's paged_kernel seam and the
    host-sampling oracle exactly like decode does."""
    model, params = tiny_model
    for kw in (dict(paged_kernel="gather"), dict(sampling="host")):
        eng = LPUEngine(model, params, slots=3, max_seq=64, paged=True,
                        block_size=16, prefill_chunk=8, **kw)
        assert eng.generate(PROMPTS, max_new_tokens=8) == mono_ref


def test_chunked_parity_multistep_windows(tiny_model, mono_ref):
    """Chunks interleave with S-step fused windows (double-buffered
    dispatch) without perturbing the streams: prefilling slots are
    frozen null-block rows inside the window."""
    model, params = tiny_model
    eng = LPUEngine(model, params, slots=3, max_seq=64, paged=True,
                    block_size=16, prefill_chunk=8, steps_per_sync=4)
    assert eng.generate(PROMPTS, max_new_tokens=8) == mono_ref


def test_chunked_parity_under_preemption(tiny_model):
    """A pool too small for the working set forces recompute preemption
    while prompts are chunk-prefilling; streams must still match the
    roomy-pool monolithic reference (recompute is exact, and a
    preempted partial prefill restarts from scratch)."""
    model, params = tiny_model
    prompts = _prompts(100, (7, 5, 3, 4))
    ref = LPUEngine(model, params, slots=3, max_seq=64, paged=True,
                    block_size=16).generate(prompts, max_new_tokens=10)
    eng = LPUEngine(model, params, slots=3, max_seq=64, paged=True,
                    block_size=8, num_blocks=4, prefill_chunk=4)
    got = eng.generate(prompts, max_new_tokens=10)
    assert eng.stats.preemptions > 0, "pool was meant to force preemption"
    assert got == ref


def test_mid_prefill_preemption_restarts_cleanly(tiny_model):
    """Preempt a prompt while only PART of it is resident: the victim's
    partial blocks are freed, and on re-admission the whole prompt is
    re-chunked from scratch — the stream still matches the monolithic
    reference.  The 40-token prompt is admitted FIRST (10 chunks of 4),
    so when the younger short stream's growth exhausts the 6 usable
    blocks, the newest-victim rule evicts the long sequence mid-chunk
    sequence (partial KV only, no sampled token yet)."""
    model, params = tiny_model
    long_prompt, short_prompt = _prompts(308, (40, 3))
    refs = {}
    refs[0] = LPUEngine(model, params, slots=2, max_seq=64, paged=True,
                        block_size=16).generate([long_prompt],
                                                max_new_tokens=4)[0]
    refs[1] = LPUEngine(model, params, slots=2, max_seq=64, paged=True,
                        block_size=16).generate([short_prompt],
                                                max_new_tokens=20)[0]
    eng = LPUEngine(model, params, slots=2, max_seq=64, paged=True,
                    block_size=8, num_blocks=7, prefill_chunk=4)
    r0 = eng.submit(long_prompt, max_new_tokens=4)
    r1 = eng.submit(short_prompt, max_new_tokens=20)
    got = eng.drain()
    assert eng.stats.preemptions > 0, "pool was meant to force preemption"
    # the long prompt was re-chunked after eviction: strictly more chunk
    # launches than one clean pass over both prompts (10 + 1)
    assert eng.stats.prefill_chunks > 11
    assert got[r0] == refs[0] and got[r1] == refs[1]


def test_prefill_chunk_requires_paged(tiny_model):
    model, params = tiny_model
    with pytest.raises(ValueError, match="paged"):
        LPUEngine(model, params, slots=2, max_seq=64, paged=False,
                  prefill_chunk=8)


# ---------------------------------------------------------------------------
# interleave: decode never stalls while a long prompt admits
# ---------------------------------------------------------------------------

def test_decode_stall_regression(tiny_model):
    """While a long prompt becomes resident chunk by chunk, every
    in-flight stream must produce exactly one token per step() — the
    regression this pins is the engine freezing decode for a full
    bucketed prefill (which the monolithic baseline measurably does on
    the same trace)."""
    model, params = tiny_model

    def run(prefill_chunk):
        got = {}

        def cb(rid, tok):
            got.setdefault(rid, []).append(tok)

        p0, p1, p_long = _prompts(208, (3, 4, 64))
        eng = LPUEngine(model, params, slots=3, max_seq=128, paged=True,
                        block_size=16, prefill_chunk=prefill_chunk)
        r0 = eng.submit(p0, max_new_tokens=40, stream_cb=cb)
        r1 = eng.submit(p1, max_new_tokens=40, stream_cb=cb)
        for _ in range(3):
            eng.step()
        r2 = eng.submit(p_long, max_new_tokens=4, stream_cb=cb)
        stalled = 0
        for _ in range(40):
            before = (len(got.get(r0, [])), len(got.get(r1, [])))
            eng.step()
            after = (len(got.get(r0, [])), len(got.get(r1, [])))
            if after == before:
                stalled += 1
            if r2 in got:
                break
        while eng.sched.has_work():
            eng.step()
        eng.drain()
        return eng, got, stalled, (r0, r1, r2)

    chunked, got_c, stalled_c, rids = run(prefill_chunk=8)
    assert chunked.stats.decode_stalls == 0
    # every step of the long prompt's 8-chunk residency also advanced
    # the short streams — no step left them without a new token
    assert stalled_c == 0, \
        f"{stalled_c} steps produced no tokens on active streams"
    mono, got_m, _, _ = run(prefill_chunk=0)
    assert mono.stats.decode_stalls >= 1, \
        "monolithic baseline should stall decode on the long admission"
    # scheduling differs, per-request streams must not
    assert all(got_c[r] == got_m[r] for r in rids)


# ---------------------------------------------------------------------------
# streamline entry: chunk == sequential single-token decode
# ---------------------------------------------------------------------------

def test_streamline_chunk_layer_matches_sequential_decode():
    """The chunk-as-batch reuse of the paged decode fold is exact: one
    chunk_prefill_layer call over S tokens equals feeding the same
    tokens one at a time through decode_layer (same pool, same table),
    including a chunk boundary that is NOT a block boundary."""
    from repro.core.streamline import chunk_prefill_layer, decode_layer
    from repro.models.common import InitCtx
    from repro.models.transformer import init_layer

    cfg = get_config("smollm-135m").reduced()
    plan = plan_model(cfg, None, (1,), "serve", esl_overlap=False,
                      remat="none", compute_dtype="float32",
                      param_dtype="float32")
    ctx = InitCtx(jax.random.PRNGKey(0), param_dtype=jnp.float32)
    p = init_layer(ctx, cfg, plan, 0)
    a = plan.attn
    bs, T = 8, 4
    table = jnp.arange(1, T + 1, dtype=jnp.int32)
    S, C = 13, 8                      # 2 chunks; second is padded
    xs = jax.random.normal(jax.random.PRNGKey(1), (S, cfg.d_model))

    pool = {"k": jnp.zeros((T + 1, a.gp, bs, a.d_head)),
            "v": jnp.zeros((T + 1, a.gp, bs, a.d_head))}
    ys, cache = [], pool
    for i in range(S):
        y, cache = decode_layer(p, xs[i:i + 1], cache,
                                jnp.asarray([i], jnp.int32), cfg=cfg,
                                plan=plan, use_kernels=False,
                                block_table=table[None])
        ys.append(np.asarray(y[0]))

    cache_ch = pool
    y1, cache_ch = chunk_prefill_layer(
        p, xs[:C], cache_ch, table, jnp.int32(0), jnp.int32(C),
        cfg=cfg, plan=plan, use_kernels=False)
    chunk2 = jnp.concatenate(
        [xs[C:], jnp.zeros((2 * C - S, cfg.d_model))])
    y2, cache_ch = chunk_prefill_layer(
        p, chunk2, cache_ch, table, jnp.int32(C), jnp.int32(S - C),
        cfg=cfg, plan=plan, use_kernels=False)
    y_chunk = np.concatenate([np.asarray(y1), np.asarray(y2)[:S - C]])
    np.testing.assert_allclose(np.stack(ys), y_chunk, rtol=1e-5,
                               atol=1e-5)
    # the resident KV itself agrees (padded rows hit null block 0) to a
    # few ULP: the chunk's K/V rows come out of an 8-row projection, the
    # sequential ones out of 1-row projections, which XLA fuses apart
    for key in ("k", "v"):
        ref = np.asarray(cache[key][1:])
        np.testing.assert_allclose(
            np.asarray(cache_ch[key][1:]), ref, rtol=0,
            atol=4 * np.spacing(np.abs(ref).max()))


def test_streamline_chunk_layer_kernel_parity():
    """use_kernels=True (Pallas gemv + paged kernel, interpret mode)
    matches the jnp oracle for the same chunk."""
    from repro.core.streamline import chunk_prefill_layer
    from repro.models.common import InitCtx
    from repro.models.transformer import init_layer

    cfg = get_config("smollm-135m").reduced()
    plan = plan_model(cfg, None, (1,), "serve", esl_overlap=False,
                      remat="none", compute_dtype="float32",
                      param_dtype="float32")
    ctx = InitCtx(jax.random.PRNGKey(0), param_dtype=jnp.float32)
    p = init_layer(ctx, cfg, plan, 0)
    a = plan.attn
    bs, T, C = 8, 4, 8
    table = jnp.arange(1, T + 1, dtype=jnp.int32)
    xs = jax.random.normal(jax.random.PRNGKey(1), (C, cfg.d_model))
    pool = {"k": jnp.zeros((T + 1, a.gp, bs, a.d_head)),
            "v": jnp.zeros((T + 1, a.gp, bs, a.d_head))}
    y_k, c_k = chunk_prefill_layer(p, xs, pool, table, jnp.int32(0),
                                   jnp.int32(C), cfg=cfg, plan=plan,
                                   use_kernels=True, interpret=True)
    y_r, c_r = chunk_prefill_layer(p, xs, pool, table, jnp.int32(0),
                                   jnp.int32(C), cfg=cfg, plan=plan,
                                   use_kernels=False)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(c_k["k"]), np.asarray(c_r["k"]),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# ring tp: chunked prefill inside the shard_map engine
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_ring_chunked_matches_dense_tp1():
    """tp=2 shard_map engine with chunked prefill (chunk KV scattered
    into per-rank head-sharded pools through replicated tables) must
    produce bit-identical token streams to the tp=1 dense engine."""
    from tests.util import run_multidevice
    out = run_multidevice("""
    import jax, numpy as np
    from repro.compiler.mapper import plan_model
    from repro.configs import get_config
    from repro.launch.mesh import make_serving_mesh
    from repro.models.registry import build_model
    from repro.serving.engine import LPUEngine

    cfg = get_config('smollm-135m').reduced()
    plan1 = plan_model(cfg, None, (1,), 'serve', esl_overlap=False,
                       remat='none', compute_dtype='float32',
                       param_dtype='float32')
    m1 = build_model(cfg, plan1)
    p1, _ = m1.init(jax.random.PRNGKey(0))
    plan2 = plan_model(cfg, ('model',), (2,), 'serve', esl_overlap=True,
                       remat='none', compute_dtype='float32',
                       param_dtype='float32')
    m2 = build_model(cfg, plan2)
    p2, _ = m2.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(11)     # margin-robust trace, see
    prompts = [list(map(int, rng.randint(1, 512, size=n)))  # _prompts
               for n in (7, 5, 3, 4, 39)]
    ref = LPUEngine(m1, p1, slots=3, max_seq=64, paged=False).generate(
        prompts, max_new_tokens=8)
    mesh = make_serving_mesh(tp=2, rings=1)
    eng = LPUEngine(m2, p2, slots=3, max_seq=64, paged=True,
                    block_size=16, mesh=mesh, prefill_chunk=8)
    got = eng.generate(prompts, max_new_tokens=8)
    assert got == ref, (got, ref)
    assert eng.stats.decode_stalls == 0
    assert eng.stats.prefill_chunks > 0
    print('PASS')
    """, n_devices=2)
    assert "PASS" in out
