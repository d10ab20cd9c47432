"""Speculative decoding (draft-and-verify): the correctness gates.

A drafter proposes k tokens per decode slot; ONE chunk-as-batch verify
pass scores all k+1 positions against the paged pool and on-device
rejection sampling accepts a per-slot prefix.  These tests pin the
contract that makes speculation safe to enable by default:

* **Greedy bit-parity** — speculative token streams are bit-identical
  to the non-speculative engine on every axis: draft_k in {1, 2, 4},
  streamed/gather paged kernels, fused/host sampling, chunked prefill,
  prefix caching, mid-stream preemption, and the tp=2 ring engine.
* **Statistical correctness** — emitted tokens are EXACT draws from
  the target distribution under temperature/top-k/top-p, regardless of
  what the drafter proposed: per-position marginals, the accept
  probability, and engine-level outcome frequencies are all bounded
  against the non-speculative sampler (TV distance).
* **Rollback accounting** — forced-rejection windows leak nothing:
  pool refcounts, free-list size and the prefix-cache index match a
  non-speculative run, including rejected writes aimed at CoW-shared
  blocks.
* **Lookahead reservation** — an all-accept window landing at a block
  boundary writes into freshly reserved blocks, never the null block
  (the ``reserve_lookahead(draft_k=...)`` regression).
* **Nucleus regression** — the top-p cutoff bug this suite's
  statistical gate caught (every non-tied row collapsed to argmax)
  stays fixed.
"""
import math
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compiler.mapper import plan_model
from repro.configs import get_config
from repro.models.registry import build_model
from repro.serving.drafter import NGramDrafter, make_drafter
from repro.serving.engine import LPUEngine, Request
from repro.serving.kv_cache import BlockPool
from repro.serving.sampler import (SamplingParams, _filter_row,
                                   sample_local, spec_verify_rows,
                                   split_spec_rng_chain)
from repro.serving.scheduler import Scheduler

VOCAB = 512     # smollm reduced()


def _prompts(seed, ns):
    """Seeded random prompts; seeds picked for robust greedy top-2
    margins (XLA CPU GEMM blocking is thread-dependent)."""
    rng = np.random.RandomState(seed)
    return [list(map(int, rng.randint(1, VOCAB, size=n))) for n in ns]


def _shared_prompts(seed, sys_len, tails):
    """A shared system prompt + random tails, final request the bare
    prompt itself (forces a tail prefill into a shared block — the
    copy-on-write shape, mirroring test_prefix_cache)."""
    rng = np.random.RandomState(seed)
    sysp = list(map(int, rng.randint(1, VOCAB, size=sys_len)))
    return [sysp + list(map(int, rng.randint(1, VOCAB, size=n)))
            for n in tails] + [list(sysp)]


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("smollm-135m").reduced()
    plan = plan_model(cfg, None, (1,), "serve", esl_overlap=False,
                      remat="none", compute_dtype="float32",
                      param_dtype="float32")
    model = build_model(cfg, plan)
    params, _ = model.init(jax.random.PRNGKey(0))
    return model, params


class OracleDrafter:
    """Proposes the reference continuation — the all-accept extreme."""

    def __init__(self, prompts, outs):
        self.ref = {tuple(p): o for p, o in zip(prompts, outs)}

    def propose(self, tokens, k):
        for p, out in self.ref.items():
            if len(p) <= len(tokens) and tuple(tokens[:len(p)]) == p:
                done = len(tokens) - len(p)
                return list(out[done:done + k])
        return []


class AdversarialDrafter:
    """Proposes tokens the model will (almost) never emit — forces
    rejection-heavy windows so rollback runs constantly."""

    def propose(self, tokens, k):
        return [(int(tokens[-1]) + 101 + 17 * i) % VOCAB
                for i in range(k)]


# ---------------------------------------------------------------------------
# drafters
# ---------------------------------------------------------------------------

def test_ngram_drafter_suffix_match():
    d = NGramDrafter()
    # period-3 stream: longest-suffix match predicts the cycle
    assert d.propose([1, 2, 3, 1, 2, 3, 1, 2], 4) == [3, 1, 2, 3]
    assert d.propose([4, 4, 4, 4], 3) == [4, 4, 4]
    # cold stream: no earlier occurrence of any suffix -> no proposal
    assert d.propose([1, 2, 3, 4, 5], 4) == []
    assert d.propose([7], 4) == []
    assert d.propose([], 4) == []


def test_make_drafter_validation():
    assert make_drafter("off") is None
    assert isinstance(make_drafter("ngram"), NGramDrafter)
    with pytest.raises(ValueError):
        make_drafter("model")          # needs draft_model/draft_params
    with pytest.raises(ValueError):
        make_drafter("banana")


def test_engine_speculate_validation(tiny_model):
    model, params = tiny_model
    with pytest.raises(ValueError):
        LPUEngine(model, params, speculate="banana")
    with pytest.raises(ValueError):
        LPUEngine(model, params, speculate="ngram", draft_k=0)
    with pytest.raises(ValueError):
        LPUEngine(model, params, speculate="ngram", paged=False)


# ---------------------------------------------------------------------------
# greedy bit-parity across the engine axes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("draft_k", [1, 2, 4])
def test_greedy_parity_draft_k(tiny_model, draft_k):
    model, params = tiny_model
    prompts = _prompts(7, (12, 9, 20))
    ref = LPUEngine(model, params, slots=2, max_seq=64).generate(
        prompts, max_new_tokens=12)
    eng = LPUEngine(model, params, slots=2, max_seq=64,
                    speculate="ngram", draft_k=draft_k)
    got = eng.generate(prompts, max_new_tokens=12)
    assert got == ref
    assert eng.stats.spec_rounds > 0 and eng.stats.draft_tokens > 0


@pytest.mark.parametrize("kernel", ["stream", "gather"])
def test_greedy_parity_paged_kernel(tiny_model, kernel):
    model, params = tiny_model
    prompts = _prompts(7, (12, 9, 20))
    kw = dict(slots=2, max_seq=64, block_size=16, paged_kernel=kernel)
    ref = LPUEngine(model, params, **kw).generate(
        prompts, max_new_tokens=10)
    got = LPUEngine(model, params, speculate="ngram", draft_k=4,
                    **kw).generate(prompts, max_new_tokens=10)
    assert got == ref


def test_fused_host_identical_streams(tiny_model):
    """Fused and host verify consume the identical rng chain, so even
    STOCHASTIC speculative streams match bit for bit.  (pipeline=False
    keeps the fused FALLBACK rounds at one token per round too — the
    pipelined second window changes where the drafter is consulted,
    which is a different — equally correct — rng path, not a bug.)"""
    model, params = tiny_model
    prompts = _prompts(7, (12, 9, 20))
    sp = SamplingParams(1.0, 40, 0.9)
    outs = {}
    for mode in ("fused", "host"):
        outs[mode] = LPUEngine(
            model, params, slots=2, max_seq=64, sampling=mode,
            speculate="ngram", draft_k=3, pipeline=False,
            rng=jax.random.PRNGKey(5)).generate(
                prompts, max_new_tokens=10, params=sp)
    assert outs["fused"] == outs["host"]


def test_greedy_parity_chunked_prefill(tiny_model):
    model, params = tiny_model
    prompts = _prompts(11, (7, 5, 39))
    kw = dict(slots=2, max_seq=64, block_size=16)
    ref = LPUEngine(model, params, **kw).generate(
        prompts, max_new_tokens=10)
    eng = LPUEngine(model, params, prefill_chunk=8, speculate="ngram",
                    draft_k=4, **kw)
    got = eng.generate(prompts, max_new_tokens=10)
    assert got == ref
    assert eng.stats.prefill_chunks > 0


def test_greedy_parity_prefix_cache(tiny_model):
    model, params = tiny_model
    prompts = _shared_prompts(3, 32, (6, 9, 3))
    kw = dict(slots=2, max_seq=64, block_size=16, prefix_cache=True)
    ref_eng = LPUEngine(model, params, **kw)
    ref = ref_eng.generate(prompts, max_new_tokens=10)
    eng = LPUEngine(model, params, speculate="ngram", draft_k=4, **kw)
    got = eng.generate(prompts, max_new_tokens=10)
    assert got == ref
    assert eng.stats.prefix_hit_blocks > 0


def test_greedy_parity_mid_stream_preemption(tiny_model):
    """A pool too small for the whole trace forces recompute preemption
    mid-decode; per-request speculative streams must still match the
    non-speculative run under the same pressure."""
    model, params = tiny_model
    prompts = _prompts(7, (12, 9, 20))
    # 4 allocatable blocks: the prompts take all 4 at admission (1+1+2)
    # and every request crosses a block boundary before it finishes, so
    # both runs MUST preempt.  With 6 blocks the speculative run could
    # finish its short requests early on accepted drafts and never feel
    # the pressure, so whether it preempted hung on the drafts the
    # random weights happen to accept.
    kw = dict(slots=3, max_seq=64, block_size=16, num_blocks=5)
    ref_eng = LPUEngine(model, params, **kw)
    ref = ref_eng.generate(prompts, max_new_tokens=16)
    eng = LPUEngine(model, params, speculate="ngram", draft_k=4, **kw)
    got = eng.generate(prompts, max_new_tokens=16)
    assert got == ref
    assert ref_eng.stats.preemptions > 0 and eng.stats.preemptions > 0


# ---------------------------------------------------------------------------
# all-accept windows: the reserve_lookahead(draft_k) regression
# ---------------------------------------------------------------------------

def test_reserve_lookahead_accounts_draft_k():
    """The verify window writes KV at pos .. pos+K before the host
    knows how many drafts were accepted, so reservation must cover the
    K extra slots — an all-accept window at a block boundary must not
    scatter into the null block."""
    pool = BlockPool(8, 8)
    sched = Scheduler(2, 64, pool)
    sched.submit(Request(0, [1] * 6, 8))
    seq = sched.admit_next()
    assert seq.pos == 6 and len(seq.blocks) == 1
    assert sched.reserve_lookahead(1)            # pos 6 fits block 1
    assert len(seq.blocks) == 1
    # draft writes reach pos 9 -> a second block must be reserved
    assert sched.reserve_lookahead(1, draft_k=3)
    assert len(seq.blocks) == 2
    # all-or-nothing on shortfall: nothing allocated
    before = pool.num_free
    assert not sched.reserve_lookahead(1, draft_k=63)
    assert pool.num_free == before and len(seq.blocks) == 2


def test_all_accept_window_crosses_block_boundary(tiny_model):
    """Oracle drafter (proposes the reference continuation) on a prompt
    ending one token before a block boundary: every window is fully
    accepted and its tail tokens land past the boundary — in freshly
    reserved blocks, not the null block.  Bit-parity would break if any
    accepted draft's KV were lost."""
    model, params = tiny_model
    prompts = _prompts(3, (15, 31))
    kw = dict(slots=2, max_seq=64, block_size=16)
    ref = LPUEngine(model, params, **kw).generate(
        prompts, max_new_tokens=12)
    eng = LPUEngine(model, params, drafter=OracleDrafter(prompts, ref),
                    draft_k=4, **kw)
    got = eng.generate(prompts, max_new_tokens=12)
    assert got == ref
    st = eng.stats
    assert st.acceptance_rate == 1.0
    # all-accept emits K+1 tokens per round: far fewer rounds than tokens
    assert st.spec_rounds <= math.ceil(12 / 5) + 2
    assert st.accepted_per_window > 1.0


# ---------------------------------------------------------------------------
# rollback: forced-rejection windows leak nothing
# ---------------------------------------------------------------------------

def test_rollback_leak_accounting(tiny_model):
    model, params = tiny_model
    prompts = _prompts(7, (12, 9, 20))
    eng = LPUEngine(model, params, slots=2, max_seq=64, block_size=16,
                    drafter=AdversarialDrafter(), draft_k=4)
    ref = LPUEngine(model, params, slots=2, max_seq=64,
                    block_size=16).generate(prompts, max_new_tokens=12)
    got = eng.generate(prompts, max_new_tokens=12)
    assert got == ref
    st = eng.stats
    assert st.draft_tokens > 0 and st.acceptance_rate < 1.0
    pool = eng.sched.pool
    assert all(r == 0 for r in pool.ref[1:])
    assert pool.num_free == pool.num_blocks - 1


def test_rollback_prefix_index_and_cow_intact(tiny_model):
    """Rejection-heavy speculation over CoW-shared blocks: rejected
    draft writes must never reach a block another table (or the prefix
    index) still references, and after drain the index, refcounts and
    free list match the non-speculative run exactly."""
    model, params = tiny_model
    prompts = _shared_prompts(3, 32, (6, 9, 3))
    kw = dict(slots=2, max_seq=64, block_size=16, prefix_cache=True)

    def snapshot(eng):
        pool, idx = eng.sched.pool, eng.prefix
        return (set(idx._by_hash.keys()), sorted(pool.ref),
                pool.num_free)

    ref_eng = LPUEngine(model, params, **kw)
    ref = ref_eng.generate(prompts, max_new_tokens=10)
    eng = LPUEngine(model, params, drafter=AdversarialDrafter(),
                    draft_k=4, **kw)
    got = eng.generate(prompts, max_new_tokens=10)
    assert got == ref
    assert eng.stats.acceptance_rate < 1.0
    # the bare-sys-prompt request forces decode over a shared block, so
    # the speculative run must have split copy-on-write before writing
    assert eng.stats.cow_blocks > 0
    assert snapshot(eng) == snapshot(ref_eng)


# ---------------------------------------------------------------------------
# statistical correctness: rejection sampling == target distribution
# ---------------------------------------------------------------------------

def _tv(counts_a, counts_b, n_a, n_b):
    keys = set(counts_a) | set(counts_b)
    return 0.5 * sum(abs(counts_a.get(k, 0) / n_a
                         - counts_b.get(k, 0) / n_b) for k in keys)


def test_rejection_sampling_exact_marginals():
    """Tiny-vocab marginal check of the accept/resample formula: with a
    deterministic proposal q one-hot at the draft token, P(out = x)
    must equal the filtered target p(x) EXACTLY at every position —
    accept with p(draft), else resample from p with the draft masked.
    Bounds the TV distance of 20k draws and the accept frequency."""
    V, K = 8, 2
    rows = jax.random.normal(jax.random.PRNGKey(2), (K + 1, V)) * 2.0
    temp = jnp.float32(1.0)
    tk, tp_ = jnp.int32(5), jnp.float32(0.85)
    p = np.asarray(jax.nn.softmax(jax.vmap(
        lambda r: _filter_row(r, temp, tk, tp_))(rows), -1))
    # draft each position's most likely token: the filtered p always
    # keeps it, so position 1 is conditioned on a frequent accept
    # whatever rows the installed RNG stream draws
    draft = jnp.asarray(p[:K].argmax(-1), jnp.int32)

    N = 20000
    rngs = jax.random.split(jax.random.PRNGKey(3), N)

    def one(r):
        _, keys = split_spec_rng_chain(r, jnp.ones((1,), bool), K + 1)
        return spec_verify_rows(rows, draft, keys[0], temp, tk, tp_)

    out, n_acc = jax.jit(jax.vmap(one))(rngs)
    out, n_acc = np.asarray(out), np.asarray(n_acc)

    # position 0 marginal == p0 (always emitted)
    freq0 = np.bincount(out[:, 0], minlength=V) / N
    assert 0.5 * np.abs(freq0 - p[0]).sum() < 0.02, (freq0, p[0])
    # accept frequency at position 0 == p0(draft0)
    acc0 = (out[:, 0] == int(draft[0])).mean()
    p_d0 = p[0, int(draft[0])]
    sigma = math.sqrt(p_d0 * (1 - p_d0) / N)
    # out[0]==draft0 also covers resamples that can't pick the masked
    # draft, so the frequency IS the accept probability
    assert abs(acc0 - p_d0) < 5 * sigma + 1e-3, (acc0, p_d0)
    # position 1, conditioned on the draft before it being accepted,
    # is an exact draw from p1 (independent rng per position)
    sel = out[n_acc >= 1]
    freq1 = np.bincount(sel[:, 1], minlength=V) / len(sel)
    assert 0.5 * np.abs(freq1 - p[1]).sum() < 0.03, (freq1, p[1])
    # the masked resample can never emit the rejected draft: rejected
    # position-0 outputs (out != draft AND literally rejected) exclude
    # draft0 by construction — check no other token got zero mass
    assert (freq0[p[0] > 0.01] > 0).all()


def test_greedy_verify_is_argmax_run():
    """temp <= 0: out rows are plain argmaxes and n_acc is the leading
    run of draft==argmax — the sequential greedy stream bit for bit."""
    V, K = 8, 3
    rows = jax.random.normal(jax.random.PRNGKey(4), (K + 1, V))
    am = np.asarray(jnp.argmax(rows, -1))
    draft = jnp.asarray([am[0], am[1], (am[2] + 1) % V], jnp.int32)
    _, keys = split_spec_rng_chain(jax.random.PRNGKey(0),
                                   jnp.ones((1,), bool), K + 1)
    out, n_acc = spec_verify_rows(rows, draft, keys[0], jnp.float32(0.0),
                                  jnp.int32(0), jnp.float32(1.0))
    assert np.asarray(out).tolist() == am.tolist()
    assert int(n_acc) == 2


def test_engine_stochastic_distribution_matches_nonspec(tiny_model):
    """Engine-level statistical gate: outcome frequencies of 2-token
    stochastic generations (temp=1, top_k=2 — a small joint outcome
    space) from the speculative engine match the non-speculative
    engine within TV 0.15.  The drafter proposes the GREEDY
    continuation, so it fires on every round and the drafts sit in the
    top-2 nucleus — the accept path and the masked-resample path are
    both exercised heavily."""
    model, params = tiny_model
    prompt = [7, 391, 44, 208] * 3
    sp = SamplingParams(1.0, 2, 1.0)
    N = 220
    kw0 = dict(slots=1, max_seq=32, block_size=16)
    greedy = LPUEngine(model, params, **kw0).generate(
        [prompt], max_new_tokens=2)[0]

    def collect(**kw):
        eng = LPUEngine(model, params, rng=jax.random.PRNGKey(123),
                        **kw0, **kw)
        counts = Counter()
        for _ in range(N):
            out = eng.generate([prompt], max_new_tokens=2, params=sp)[0]
            counts[tuple(out)] += 1
        return counts, eng.stats

    base, _ = collect()
    spec, st = collect(drafter=OracleDrafter([prompt], [greedy * 3]),
                       draft_k=2)
    assert st.spec_rounds > 0 and st.draft_tokens > 0
    assert st.accepted_tokens > 0
    tv = _tv(base, spec, N, N)
    assert tv < 0.15, (tv, dict(base), dict(spec))


# ---------------------------------------------------------------------------
# the top-p nucleus regression the statistical gate caught
# ---------------------------------------------------------------------------

def test_top_p_keeps_whole_nucleus():
    """The old cutoff (max of the kept prefix) collapsed every non-tied
    row to its argmax for ANY top_p < 1 — the speculative statistical
    suite caught it; this pins the fix (min of the kept prefix)."""
    lg = jnp.asarray([3.0, 2.0, 1.0, 0.0] + [-9.0] * 4)
    # p = softmax ~ [.64, .24, .09, .03, ...]: top_p=0.9 keeps 0, 1, 2
    kept = _filter_row(lg, jnp.float32(1.0), jnp.int32(0),
                       jnp.float32(0.9))
    finite = np.isfinite(np.asarray(kept))
    assert finite.tolist()[:4] == [True, True, True, False]
    toks = {int(sample_local(lg[None], jax.random.PRNGKey(i),
                             SamplingParams(1.0, 0, 0.9))[0])
            for i in range(300)}
    assert toks == {0, 1, 2}, toks


# ---------------------------------------------------------------------------
# streamline entry: verify window == sequential single-token decode
# ---------------------------------------------------------------------------

def test_streamline_verify_layer_matches_sequential_decode():
    """verify_layer's chunk-as-batch window (per-query tables and
    positions) is exact: one call over a slot's K+1 verify queries
    equals feeding them one at a time through decode_layer — including
    queries past a block boundary."""
    from repro.core.streamline import decode_layer, verify_layer
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
    S0, K1 = 6, 4                  # resident history + verify window
    xs = jax.random.normal(jax.random.PRNGKey(1),
                           (S0 + K1, cfg.d_model))

    pool = {"k": jnp.zeros((T + 1, a.gp, bs, a.d_head)),
            "v": jnp.zeros((T + 1, a.gp, bs, a.d_head))}
    cache = pool
    ys = []
    for i in range(S0 + K1):
        y, cache = decode_layer(p, xs[i:i + 1], cache,
                                jnp.asarray([i], jnp.int32), cfg=cfg,
                                plan=plan, use_kernels=False,
                                block_table=table[None])
        ys.append(np.asarray(y[0]))

    # replay: same history, then the verify window in ONE call with
    # per-query tables/positions (positions 6..9 cross the bs=8 block)
    cache_v = pool
    for i in range(S0):
        _, cache_v = decode_layer(p, xs[i:i + 1], cache_v,
                                  jnp.asarray([i], jnp.int32), cfg=cfg,
                                  plan=plan, use_kernels=False,
                                  block_table=table[None])
    tabs = jnp.broadcast_to(table, (K1, T))
    posn = S0 + jnp.arange(K1, dtype=jnp.int32)
    y_v, cache_v = verify_layer(p, xs[S0:], cache_v, tabs, posn,
                                cfg=cfg, plan=plan, use_kernels=False)
    np.testing.assert_allclose(np.stack(ys[S0:]), np.asarray(y_v),
                               rtol=1e-5, atol=1e-5)
    # the window's K/V rows come out of a 4-row projection, the
    # sequential ones out of 1-row projections, which XLA fuses apart:
    # the pools agree to a few ULP
    for key in ("k", "v"):
        ref = np.asarray(cache[key][1:])
        np.testing.assert_allclose(
            np.asarray(cache_v[key][1:]), ref, rtol=0,
            atol=4 * np.spacing(np.abs(ref).max()))


# ---------------------------------------------------------------------------
# ring tp: speculation inside the shard_map engine
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_ring_speculative_matches_dense_tp1():
    """tp=2 shard_map verify (draft KV scattered into per-rank
    head-sharded pools, candidate-set verification all-gathered) must
    produce bit-identical greedy streams to the tp=1 dense engine."""
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
    rng = np.random.RandomState(11)
    prompts = [list(map(int, rng.randint(1, 512, size=n)))
               for n in (7, 5, 12)]
    ref = LPUEngine(m1, p1, slots=2, max_seq=64, paged=False).generate(
        prompts, max_new_tokens=10)
    mesh = make_serving_mesh(tp=2, rings=1)
    eng = LPUEngine(m2, p2, slots=2, max_seq=64, paged=True,
                    block_size=16, mesh=mesh, speculate='ngram',
                    draft_k=4)
    got = eng.generate(prompts, max_new_tokens=10)
    assert got == ref, (got, ref)
    assert eng.stats.spec_rounds > 0 and eng.stats.draft_tokens > 0
    print('PASS')
    """, n_devices=2)
    assert "PASS" in out
