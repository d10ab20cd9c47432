"""Paged KV cache: block allocator, bucketing, paged/dense parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compiler.mapper import plan_model
from repro.configs import get_config
from repro.kernels.decode_attention import (decode_attention,
                                            gather_kv_pages,
                                            paged_decode_attention,
                                            paged_decode_attention_ref)
from repro.kernels.decode_attention.decode_attention import (
    decode_attention_pallas, paged_decode_attention_pallas)
from repro.models.registry import build_model
from repro.serving.engine import LPUEngine
from repro.serving.kv_cache import BlockPool, blocks_for, bucket_for


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------

def test_block_pool_alloc_free_accounting():
    pool = BlockPool(num_blocks=8, block_size=16)
    assert pool.num_free == 7                      # block 0 reserved
    a = pool.alloc(3)
    assert a is not None and len(a) == 3
    assert 0 not in a                              # null block never granted
    assert pool.num_used == 3
    assert pool.used_bytes(100) == 300
    b = pool.alloc(4)
    assert b is not None and not set(a) & set(b)
    assert pool.alloc(1) is None                   # exhausted: no grant
    pool.free(a)
    assert pool.num_free == 3
    c = pool.alloc(3)
    assert c is not None


def test_block_pool_double_free_rejected():
    pool = BlockPool(num_blocks=4, block_size=16)
    a = pool.alloc(2)
    pool.free(a)
    with pytest.raises(ValueError):
        pool.free(a)
    with pytest.raises(ValueError):
        pool.free([0])                             # null block untouchable


def test_block_pool_bad_free_rejected():
    """Freeing ids the pool never granted must raise, not silently
    corrupt another table's refcounts (blocks are shared under the
    prefix cache, so a bad free can recycle a live block)."""
    pool = BlockPool(num_blocks=4, block_size=16)
    with pytest.raises(ValueError, match="never-allocated"):
        pool.free([2])                             # in range, never granted
    with pytest.raises(ValueError, match="bad block id"):
        pool.free([4])                             # out of range
    with pytest.raises(ValueError, match="bad block id"):
        pool.free([-1])
    a = pool.alloc(1)
    pool.share(a)                                  # ref 2: two tables
    pool.free(a)
    pool.free(a)                                   # both owners release
    with pytest.raises(ValueError, match="double free"):
        pool.free(a)                               # third free is a bug
    assert pool.num_free == 3                      # accounting intact


def test_bucket_for_pow2():
    assert bucket_for(1, 256) == 16
    assert bucket_for(16, 256) == 16
    assert bucket_for(17, 256) == 32
    assert bucket_for(100, 256) == 128
    assert bucket_for(200, 256) == 256
    assert bucket_for(5, 256, min_bucket=64) == 64
    with pytest.raises(ValueError):
        bucket_for(300, 256)
    # bucket count over all lengths is O(log2 max_seq)
    buckets = {bucket_for(n, 256) for n in range(1, 257)}
    assert len(buckets) <= 5


def test_blocks_for():
    assert blocks_for(1, 16) == 1
    assert blocks_for(16, 16) == 1
    assert blocks_for(17, 16) == 2
    assert blocks_for(0, 16) == 1                  # at least one block


# ---------------------------------------------------------------------------
# paged decode attention: parity with the dense kernel
# ---------------------------------------------------------------------------

def _paged_inputs(key, B=2, H=4, G=2, dh=128, bs=128, T=4, N=9):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, dh), jnp.float32)
    k_pages = jax.random.normal(ks[1], (N, G, bs, dh), jnp.float32)
    v_pages = jax.random.normal(ks[2], (N, G, bs, dh), jnp.float32)
    tables = jnp.asarray([[1, 3, 5, 0], [2, 4, 6, 8]], jnp.int32)
    lengths = jnp.asarray([3 * bs - 5, 4 * bs - 61], jnp.int32)
    return q, k_pages, v_pages, tables, lengths


def test_paged_kernel_bit_compatible_with_dense():
    """Same tile size => identical accumulation order => bitwise equal."""
    q, kp, vp, tables, lengths = _paged_inputs(jax.random.PRNGKey(0))
    bs = kp.shape[2]
    kd = gather_kv_pages(kp, tables)
    vd = gather_kv_pages(vp, tables)
    dense = decode_attention_pallas(q, kd, vd, lengths, block_s=bs)
    paged = paged_decode_attention_pallas(q, kp, vp, tables, lengths)
    assert np.array_equal(np.asarray(dense), np.asarray(paged))


def test_paged_ops_matches_dense_ops():
    q, kp, vp, tables, lengths = _paged_inputs(jax.random.PRNGKey(1))
    kd = gather_kv_pages(kp, tables)
    vd = gather_kv_pages(vp, tables)
    dense = decode_attention(q, kd, vd, lengths)
    paged = paged_decode_attention(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(paged),
                               rtol=2e-5, atol=2e-5)


def test_paged_ref_fallback_matches_pallas():
    q, kp, vp, tables, lengths = _paged_inputs(jax.random.PRNGKey(2))
    pal = paged_decode_attention(q, kp, vp, tables, lengths)
    ref = paged_decode_attention(q, kp, vp, tables, lengths,
                                 use_pallas=False)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_ref_gqa_expansion():
    """Oracle on H-expanded pages equals grouped pallas path."""
    q, kp, vp, tables, lengths = _paged_inputs(jax.random.PRNGKey(3))
    H, G = q.shape[1], kp.shape[1]
    gs = H // G
    ke = jnp.repeat(kp, gs, axis=1)
    ve = jnp.repeat(vp, gs, axis=1)
    ref = paged_decode_attention_ref(q, ke, ve, tables, lengths)
    pal = paged_decode_attention(q, kp, vp, tables, lengths)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                               rtol=2e-5, atol=2e-5)


def test_null_block_never_contributes():
    """Table entries past the valid length (null block 0) are masked."""
    q, kp, vp, tables, lengths = _paged_inputs(jax.random.PRNGKey(4))
    bs = kp.shape[2]
    lengths = jnp.asarray([2 * bs, 3 * bs], jnp.int32)   # 2/3 blocks valid
    out1 = paged_decode_attention(q, kp, vp, tables, lengths)
    # scribble over the null block AND the unused tail blocks
    kp2 = kp.at[0].set(1e3).at[8].set(-1e3)
    vp2 = vp.at[0].set(1e3).at[8].set(-1e3)
    tables2 = tables.at[0, 3].set(0).at[1, 3].set(0)
    out2 = paged_decode_attention(q, kp2, vp2, tables2, lengths)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# engine-level parity + preemption
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("smollm-135m").reduced()
    plan = plan_model(cfg, None, (1,), "serve", esl_overlap=False,
                      remat="none", compute_dtype="float32",
                      param_dtype="float32")
    model = build_model(cfg, plan)
    params, _ = model.init(jax.random.PRNGKey(0))
    return model, params


PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [10, 11],
           [3, 1, 4, 1, 5, 9, 2, 6], [2, 7]]


def test_engine_paged_matches_dense(tiny_model):
    model, params = tiny_model
    dense = LPUEngine(model, params, slots=3, max_seq=64, paged=False)
    paged = LPUEngine(model, params, slots=3, max_seq=64, paged=True,
                      block_size=16)
    od = dense.generate(PROMPTS, max_new_tokens=8)
    op = paged.generate(PROMPTS, max_new_tokens=8)
    assert od == op
    assert paged.stats.prefill_traces <= 7       # log2(64)+1


def test_engine_pool_exhaustion_preempts(tiny_model):
    """A pool too small for all slots forces recompute preemption, and
    the outputs still match the dense engine exactly."""
    model, params = tiny_model
    dense = LPUEngine(model, params, slots=3, max_seq=64, paged=False)
    od = dense.generate(PROMPTS, max_new_tokens=20)
    # 3 slots x up to 28 resident tokens, but only 3 usable 8-tok blocks:
    # at most ~1 sequence's worth of KV is resident at a time
    paged = LPUEngine(model, params, slots=3, max_seq=64, paged=True,
                      block_size=8, num_blocks=5)
    op = paged.generate(PROMPTS, max_new_tokens=20)
    assert paged.stats.preemptions > 0
    assert od == op


def test_engine_single_seq_pool_overflow_raises(tiny_model):
    model, params = tiny_model
    eng = LPUEngine(model, params, slots=2, max_seq=64, paged=True,
                    block_size=8, num_blocks=3)   # 2 usable blocks = 16 tok
    with pytest.raises(RuntimeError):
        eng.generate([[1, 2, 3]], max_new_tokens=30)


def test_engine_prompt_longer_than_pool_rejected(tiny_model):
    model, params = tiny_model
    eng = LPUEngine(model, params, slots=2, max_seq=64, paged=True,
                    block_size=8, num_blocks=3)
    with pytest.raises(ValueError):
        eng.submit(list(range(1, 30)), max_new_tokens=4)


def test_scheduler_impossible_resume_rejected():
    """A preempted request whose resume state outgrew the pool must be
    popped with a structured reason instead of livelocking the admission
    loop (or crashing the whole engine over one doomed request)."""
    from repro.serving.scheduler import Scheduler

    class FakeReq:
        rid = 0
        prompt = list(range(10))
        out = list(range(30))

        def resume_tokens(self):
            return self.prompt + self.out[:-1]    # 39 tokens > 24-tok pool

    sched = Scheduler(2, 64, BlockPool(4, 8))     # 3 usable blocks
    sched.queue.append(FakeReq())                 # as if re-queued
    assert sched.admit_next() is None             # no crash, no livelock
    rejected = sched.take_rejected()
    assert len(rejected) == 1 and rejected[0][0].rid == 0
    assert "blocks" in rejected[0][1]
    assert not sched.queue                        # popped, not spun on


def test_paged_pool_smaller_than_dense(tiny_model):
    model, params = tiny_model
    eng = LPUEngine(model, params, slots=4, max_seq=64, paged=True,
                    block_size=16, num_blocks=9)   # half dense capacity
    outs = eng.generate(PROMPTS, max_new_tokens=8)
    assert all(len(o) == 8 for o in outs)
    assert eng.kv_cache_bytes() < eng.dense_equiv_bytes()
