"""Fused on-device sampling + multi-step decode windows (engine level).

The engine's default decode program now samples in-jit and can run
``steps_per_sync`` decode steps per host readback (one lax.scan window
with on-device stop masking).  These tests pin the PR's contract:

* token streams are IDENTICAL to the pre-fusion host-sampled engine —
  greedy bit-for-bit (dense, paged stream/gather, steps_per_sync 1 and
  4, under preemption and eos), and stochastic runs with a fixed rng,
  including mixed per-slot SamplingParams and mid-window finishes;
* only O(slots) bytes cross to the host per token (the logits row
  never does), and multi-step windows cut host syncs ~Sx;
* speculative lookahead never preempts resident work.
"""
import jax
import numpy as np
import pytest

from repro.compiler.mapper import plan_model
from repro.configs import get_config
from repro.kernels.decode_attention.ops import plan_block_s
from repro.models.registry import build_model
from repro.serving.engine import LPUEngine
from repro.serving.sampler import SamplingParams

PROMPTS = [[1, 2, 3, 4, 5, 6, 7], [8, 9, 10, 11, 12], [13, 14, 15],
           [16, 17, 18, 19]]


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
def greedy_ref(tiny_model):
    model, params = tiny_model
    return LPUEngine(model, params, slots=3, max_seq=64, paged=False,
                     sampling="host").generate(PROMPTS, max_new_tokens=10)


# -- greedy bit-parity with the pre-fusion engine ----------------------

@pytest.mark.parametrize("steps", [1, 4])
@pytest.mark.parametrize("kern", ["dense", "stream", "gather",
                                  "stream-b128"])
def test_fused_greedy_matches_host(tiny_model, greedy_ref, kern, steps):
    """``stream-b128``: 128-token blocks of 32-wide heads, which the
    stacked-pool kernel reads as (dh, bs) planes."""
    model, params = tiny_model
    kw = dict(max_seq=64)
    if kern == "dense":
        kw.update(paged=False)
    elif kern == "stream-b128":
        kw.update(paged=True, max_seq=128, block_size=128,
                  paged_kernel="stream")
    else:
        kw.update(paged=True, block_size=16, paged_kernel=kern)
    eng = LPUEngine(model, params, slots=3, steps_per_sync=steps, **kw)
    assert eng.generate(PROMPTS, max_new_tokens=10) == greedy_ref
    # the fused engine never reads a logits row: O(slots) bytes/token
    assert eng.stats.bytes_to_host_per_token <= 8 * eng.slots + 16


def test_fused_multistep_parity_under_preemption(tiny_model, greedy_ref):
    """A pool too small for the working set: windows must degrade to
    single steps (reserve_lookahead never preempts) and recompute
    preemption must still reproduce the dense streams exactly."""
    model, params = tiny_model
    eng = LPUEngine(model, params, slots=3, max_seq=64, paged=True,
                    block_size=8, num_blocks=4, steps_per_sync=4)
    assert eng.generate(PROMPTS, max_new_tokens=10) == greedy_ref
    assert eng.stats.preemptions > 0, "pool was meant to force preemption"


def test_fused_eos_mid_window(tiny_model, greedy_ref):
    """EOS inside a 4-step window: the device masks the slot, the host
    discards its overrun tokens, and the streams match the single-step
    host engine exactly."""
    model, params = tiny_model
    base = greedy_ref[0]
    k = next((i for i in range(1, len(base)) if base[i] not in base[:i]),
             None)
    if k is None:
        pytest.skip("degenerate greedy output: no unique mid-flight token")
    eos = base[k]
    ref = LPUEngine(model, params, slots=2, max_seq=64, eos_id=eos,
                    sampling="host").generate(PROMPTS[:3],
                                              max_new_tokens=10)
    eng = LPUEngine(model, params, slots=2, max_seq=64, eos_id=eos,
                    steps_per_sync=4)
    assert eng.generate(PROMPTS[:3], max_new_tokens=10) == ref
    assert ref[0] == base[:k + 1]


# -- stochastic parity (fixed rng) -------------------------------------

def _run_mixed(model, params, sampling, steps):
    """Mixed per-slot SamplingParams with staggered budgets so slots
    finish mid-window; requests <= slots so the rng-split schedule is
    admission-order independent."""
    eng = LPUEngine(model, params, slots=3, max_seq=64,
                    rng=jax.random.PRNGKey(11), sampling=sampling,
                    steps_per_sync=steps)
    spec = [(PROMPTS[0], 9, SamplingParams(0.0, 0, 1.0)),
            (PROMPTS[1], 5, SamplingParams(0.8, 7, 1.0)),
            (PROMPTS[2], 12, SamplingParams(1.1, 0, 0.9))]
    rids = [eng.submit(p, n, sp) for p, n, sp in spec]
    res = eng.drain()
    return [res[r] for r in rids]


@pytest.mark.parametrize("steps", [1, 4])
def test_fused_stochastic_mixed_params_matches_host(tiny_model, steps):
    model, params = tiny_model
    want = _run_mixed(model, params, "host", 1)
    got = _run_mixed(model, params, "fused", steps)
    assert got == want
    assert [len(o) for o in got] == [9, 5, 12]   # mid-window finishes


def test_fused_stochastic_reproducible(tiny_model):
    model, params = tiny_model
    a = _run_mixed(model, params, "fused", 4)
    b = _run_mixed(model, params, "fused", 4)
    assert a == b


# -- host-sync / bytes accounting --------------------------------------

def test_sync_accounting_fused_vs_host(tiny_model):
    model, params = tiny_model
    host = LPUEngine(model, params, slots=3, max_seq=64, sampling="host")
    host.generate(PROMPTS, max_new_tokens=8)
    fused = LPUEngine(model, params, slots=3, max_seq=64,
                      steps_per_sync=4)
    fused.generate(PROMPTS, max_new_tokens=8)
    v = model.cfg.vocab_size
    # host path ships >= one fp32 logits row per decode token
    assert host.stats.bytes_to_host_per_token >= 4 * v
    # fused path ships O(slots) int32 ids (+ window slack), not O(vocab)
    assert fused.stats.bytes_to_host_per_token <= 8 * fused.slots + 16
    assert fused.stats.bytes_to_host_per_token * 50 < \
        host.stats.bytes_to_host_per_token
    # multi-step windows sync strictly less often
    assert fused.stats.host_syncs < host.stats.host_syncs
    assert fused.stats.tokens == host.stats.tokens


def test_reserve_lookahead_never_preempts(tiny_model):
    """Window reservation is all-or-nothing and preemption-free."""
    model, params = tiny_model
    eng = LPUEngine(model, params, slots=2, max_seq=64, paged=True,
                    block_size=8, num_blocks=5, pipeline=False)
    eng.submit(PROMPTS[0], max_new_tokens=40)
    eng.step()                          # admit + prefill + one decode
    sched = eng.sched
    assert sched.num_active() == 1
    free0, pre0 = sched.pool.num_free, sched.preemptions
    ok = sched.reserve_lookahead(1000)            # cannot possibly fit
    assert not ok
    assert sched.pool.num_free == free0, "failed reserve must not alloc"
    assert sched.preemptions == pre0, "reserve must never preempt"
    assert sched.reserve_lookahead(1)             # the next step still fits


# -- configuration validation ------------------------------------------

def test_engine_rejects_invalid_dispatch_configs(tiny_model):
    model, params = tiny_model
    with pytest.raises(ValueError):
        LPUEngine(model, params, sampling="turbo")
    with pytest.raises(ValueError):
        LPUEngine(model, params, steps_per_sync=0)
    with pytest.raises(ValueError):
        LPUEngine(model, params, sampling="host", steps_per_sync=4)
    with pytest.raises(ValueError):
        # streamed paged tile IS the pool block size
        LPUEngine(model, params, max_seq=64, paged=True, block_size=16,
                  paged_kernel="stream", block_s=32)


# -- block_s override (--block-s) --------------------------------------

def test_plan_block_s_override():
    assert plan_block_s(4096, 128, 4) == 4096
    assert plan_block_s(4096, 128, 4, override=512) == 512
    assert plan_block_s(256, 128, 4, override=1024) == 256  # clamped
    with pytest.raises(ValueError):
        plan_block_s(4096, 128, 4, override=100)            # not a tile
    with pytest.raises(ValueError):
        plan_block_s(256, 128, 4, override=8)    # tiles, but not LANE-ok
    assert plan_block_s(64, 128, 4, override=64) == 64  # full span exempt
    assert plan_block_s(4096, 128, 4, override=0) == 4096   # 0 = planned


def test_engine_block_s_override_still_serves(tiny_model):
    model, params = tiny_model
    eng = LPUEngine(model, params, slots=2, max_seq=64, paged=False,
                    block_s=32)
    outs = eng.generate(PROMPTS[:2], max_new_tokens=5)
    assert all(len(o) == 5 for o in outs)
    assert eng.decode_block_s() == 32
    assert eng.planned_block_s() >= 1
    # default engines report the planned/structural tile
    deflt = LPUEngine(model, params, slots=2, max_seq=64, paged=True,
                      block_size=16)
    assert deflt.decode_block_s() == 16      # stream tile == pool block


# -- the measured no-copy gate survives the fused program --------------

def test_fused_window_program_view_tensor_gate(tiny_model):
    """The per-request contiguous KV view must not appear in the fused
    streamed window program (and must appear in the gather oracle's)."""
    model, params = tiny_model
    a = model.plan.attn
    sig = f"tensor<2x64x{a.gp}x{a.d_head}xf32>"
    kw = dict(slots=2, max_seq=64, paged=True, block_size=16)
    stream = LPUEngine(model, params, paged_kernel="stream", **kw)
    gather = LPUEngine(model, params, paged_kernel="gather", **kw)
    assert stream.lower_decode_text().count(sig) == 0
    assert gather.lower_decode_text().count(sig) > 0


# -- ring parallelism: fused tp=2 == host tp=1 -------------------------

@pytest.mark.slow
def test_ring_fused_sampling_matches_dense_tp1():
    """tp=2 shard_map engine with fused in-ring sampling
    (sample_sharded_batched: only (tp x k) candidates are gathered, the
    full vocab row never leaves the ranks) must match the tp=1 dense
    host-sampled engine bit-for-bit, for steps_per_sync 1 and 4."""
    from tests.util import run_multidevice
    out = run_multidevice("""
    import jax
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
    prompts = [[1,2,3,4,5,6,7],[8,9,10,11,12],[13,14,15],[16,17,18,19]]
    ref = LPUEngine(m1, p1, slots=3, max_seq=64, paged=False,
                    sampling='host').generate(prompts, max_new_tokens=10)
    mesh = make_serving_mesh(tp=2, rings=1)
    for S in (1, 4):
        eng = LPUEngine(m2, p2, slots=3, max_seq=64, paged=True,
                        block_size=16, mesh=mesh, steps_per_sync=S)
        got = eng.generate(prompts, max_new_tokens=10)
        assert got == ref, (S, got, ref)
        assert eng.stats.bytes_to_host_per_token <= 8 * 3 + 16
    engd = LPUEngine(m2, p2, slots=3, max_seq=64, paged=False, mesh=mesh,
                     steps_per_sync=4)
    assert engd.generate(prompts, max_new_tokens=10) == ref
    print('PASS')
    """, n_devices=2)
    assert "PASS" in out
