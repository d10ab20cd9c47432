"""Streamed paged decode: the Pallas kernel consumes KV tiles straight
from the block pool (scalar-prefetched tables, new token folded into the
online-softmax carry) — parity against the gather oracle and the dense
engine, including under recompute preemption, plus the null-block
property."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compiler.mapper import plan_model
from repro.compiler.plan import plan_attention
from repro.configs import get_config
from repro.core.streamline import decode_layer
from repro.kernels.decode_attention.ops import paged_decode_attention
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                gather_kv_pages)
from repro.models.attention import paged_stream_supported
from repro.models.common import InitCtx
from repro.models.registry import build_model
from repro.models.transformer import init_layer
from repro.serving.engine import LPUEngine


# ---------------------------------------------------------------------------
# kernel level: in-kernel fold of the just-generated token
# ---------------------------------------------------------------------------

def _fold_inputs(key, B=2, H=4, G=2, dh=16, bs=8, T=4, N=9):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, H, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (N, G, bs, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (N, G, bs, dh), jnp.float32)
    k_new = jax.random.normal(ks[3], (B, G, dh), jnp.float32)
    v_new = jax.random.normal(ks[4], (B, G, dh), jnp.float32)
    tables = jnp.asarray(np.arange(1, B * T + 1, dtype=np.int32)
                         .reshape(B, T))
    lengths = jnp.asarray([13, 27], jnp.int32)
    return q, kp, vp, k_new, v_new, tables, lengths


def test_kernel_fold_matches_scatter_oracle():
    """Folding (k_new, v_new) into the carry == scattering the new token
    at position ``length`` and attending over lengths+1."""
    q, kp, vp, kn, vn, tables, lengths = _fold_inputs(jax.random.PRNGKey(0))
    B, H = q.shape[:2]
    gs = H // kp.shape[1]
    folded = paged_decode_attention(q, kp, vp, tables, lengths,
                                    k_new=kn, v_new=vn)
    ke = jnp.repeat(gather_kv_pages(kp, tables), gs, axis=2)
    ve = jnp.repeat(gather_kv_pages(vp, tables), gs, axis=2)
    ke = ke.at[jnp.arange(B), lengths].set(jnp.repeat(kn, gs, axis=1))
    ve = ve.at[jnp.arange(B), lengths].set(jnp.repeat(vn, gs, axis=1))
    ref = decode_attention_ref(q, ke, ve, lengths + 1)
    np.testing.assert_allclose(np.asarray(folded), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_kernel_fold_fallback_matches_pallas():
    """The use_pallas=False oracle (mask-scatter pre-kernel) agrees with
    the in-kernel fold."""
    q, kp, vp, kn, vn, tables, lengths = _fold_inputs(jax.random.PRNGKey(1))
    pal = paged_decode_attention(q, kp, vp, tables, lengths,
                                 k_new=kn, v_new=vn)
    ref = paged_decode_attention(q, kp, vp, tables, lengths,
                                 k_new=kn, v_new=vn, use_pallas=False)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["kernel", "gather"])
@pytest.mark.parametrize("dh", [64, 128])
@pytest.mark.parametrize("store", ["bfloat16", "int8"])
def test_stacked_pool_matches_sliced_layer(store, dh, use_pallas):
    """Layer ``l`` of a stacked (L, N, G, bs, dh) pool, read through the
    layer index, equals the 4-D call on ``pool[l]`` bit for bit — the
    kernel (at d_head 64 with block 128 through the (dh, bs) planes the
    TPU stores, at d_head 128 through (bs, dh) ones) and the gather
    oracle, with an int8 pool's stacked scales too."""
    from repro.kernels.decode_attention.decode_attention import \
        lane_dense_planes
    from repro.serving.kv_cache import quantize_kv_rows
    L, N, G, H, bs, B = 3, 7, 2, 4, 128, 3
    assert lane_dense_planes(bs, dh) == (dh == 64)
    ks = jax.random.split(jax.random.PRNGKey(dh), 5)
    kp = jax.random.normal(ks[0], (L, N, G, bs, dh), jnp.float32)
    vp = jax.random.normal(ks[1], (L, N, G, bs, dh), jnp.float32)
    if store == "int8":
        kp, k_sc = quantize_kv_rows(kp, jnp.int8, jnp.bfloat16)
        vp, v_sc = quantize_kv_rows(vp, jnp.int8, jnp.bfloat16)
        scales = {"k_scale": k_sc, "v_scale": v_sc}
    else:
        kp, vp, scales = kp.astype(store), vp.astype(store), {}
    q = jax.random.normal(ks[2], (B, H, dh), jnp.bfloat16)
    kn = jax.random.normal(ks[3], (B, G, dh), jnp.bfloat16)
    vn = jax.random.normal(ks[4], (B, G, dh), jnp.bfloat16)
    tables = jnp.asarray([[1, 2], [3, 4], [5, 0]], jnp.int32)
    lengths = jnp.asarray([200, 77, 5], jnp.int32)
    for l in range(L):
        stacked = paged_decode_attention(
            q, kp, vp, tables, lengths, layer=jnp.int32(l), k_new=kn,
            v_new=vn, use_pallas=use_pallas, **scales)
        sliced = paged_decode_attention(
            q, kp[l], vp[l], tables, lengths, k_new=kn, v_new=vn,
            use_pallas=use_pallas, **{k: v[l] for k, v in scales.items()})
        np.testing.assert_array_equal(np.asarray(stacked),
                                      np.asarray(sliced))


def test_pool_rank_picks_the_call():
    """A stacked pool needs its layer index and a 4-D pool refuses one:
    the rank of the pool is the whole dispatch."""
    q, kp, vp, _, _, tables, lengths = _fold_inputs(jax.random.PRNGKey(2))
    with pytest.raises(ValueError, match="layer index"):
        paged_decode_attention(q, kp[None], vp[None], tables, lengths)
    with pytest.raises(ValueError, match="layer index"):
        paged_decode_attention(q, kp, vp, tables, lengths,
                               layer=jnp.int32(0))


# ---------------------------------------------------------------------------
# layout gate: which plans may stream
# ---------------------------------------------------------------------------

def test_block_regular_layouts():
    # sharded GQA (n_kv >= tp): regular on every rank
    assert plan_attention(16, 4, 64, tp=4).block_regular
    # duplicated single kv head per rank: trivially regular
    assert plan_attention(8, 1, 64, tp=2).block_regular
    # dup>1 with multiple kv heads per rank and padding misalignment:
    # rank 0 holds q heads [0,1] both mapping kv 0 — NOT i//gs regular
    assert not plan_attention(8, 4, 64, tp=6).block_regular


def test_stream_supported_matches_plan():
    cfg = get_config("smollm-135m").reduced()
    plan = plan_model(cfg, None, (1,), "serve", esl_overlap=False,
                      remat="none", compute_dtype="float32",
                      param_dtype="float32")
    assert paged_stream_supported(plan) == plan.attn.block_regular


def test_stream_support_alignment_gate_compiled():
    """The head-major pool hands the compiled kernel whole (bs, dh)
    planes, so no block size or head width gates streaming (the AOT
    compiles in test_tpu_compile.py hold the kernel to that); only the
    stored GQA layout does, and an ineligible explicit request raises
    instead of degrading to gather."""
    import dataclasses
    from repro.kernels.decode_attention.ops import resolve_paged_kernel
    cfg = get_config("smollm-135m").reduced()
    plan = plan_model(cfg, None, (1,), "serve", esl_overlap=False,
                      remat="none", compute_dtype="float32",
                      param_dtype="float32")
    assert plan.attn.block_regular and plan.attn.d_head % 128
    assert paged_stream_supported(plan)
    assert resolve_paged_kernel(plan, "auto") == "stream"
    model = build_model(cfg, plan)
    params, _ = model.init(jax.random.PRNGKey(0))
    eng = LPUEngine(model, params, slots=2, max_seq=64, paged=True,
                    block_size=16)
    assert eng.paged_kernel == "stream"
    bad_plan = dataclasses.replace(plan, attn=plan_attention(8, 4, 64, tp=6))
    assert resolve_paged_kernel(bad_plan, "auto") == "gather"
    with pytest.raises(ValueError):
        resolve_paged_kernel(bad_plan, "stream")


# ---------------------------------------------------------------------------
# model level: forward(mode='decode') stream vs gather over the same pool
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


def test_forward_stream_matches_gather(tiny_model):
    model, params = tiny_model
    from repro.core.dist import make_axis_env
    B, bs, nb, max_seq = 3, 16, 13, 64
    env = make_axis_env(model.plan, batch=B)
    cache = model.init_cache(B, max_seq, paged=True, num_blocks=nb,
                             block_size=bs)
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 64))
    cache = jax.tree.map(
        lambda c: jax.random.normal(next(keys), c.shape, c.dtype), cache)
    tables = jnp.asarray(np.arange(1, B * 4 + 1, dtype=np.int32)
                         .reshape(B, 4))
    tokens = jnp.asarray([[5], [9], [2]], jnp.int32)
    positions = jnp.asarray([3, 17, 40], jnp.int32)
    res = {}
    for mode in ("stream", "gather"):
        logits, upd, _ = model.forward(
            params, tokens, env=env, mode="decode", positions=positions,
            cache=cache, block_tables=tables, paged_kernel=mode)
        res[mode] = (np.asarray(logits), upd)
    np.testing.assert_allclose(res["stream"][0], res["gather"][0],
                               rtol=2e-5, atol=2e-5)
    # the cache-update contract is the same in both modes (read the pool
    # pre-update, scatter the new KV rows into the scan carry); rows
    # written by layers > 0 inherit the tiny tiling-order differences of
    # the previous layer's attention output, hence allclose, not equal
    for a, b in zip(jax.tree.leaves(res["stream"][1]),
                    jax.tree.leaves(res["gather"][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_forward_stream_rejects_irregular_plan(tiny_model):
    """An irregular stored layout cannot stream — the seam must refuse
    explicitly rather than silently compute wrong head groupings."""
    import dataclasses
    model, _ = tiny_model
    bad_plan = dataclasses.replace(model.plan,
                                   attn=plan_attention(8, 4, 64, tp=6))
    assert not paged_stream_supported(bad_plan)


# ---------------------------------------------------------------------------
# streamline (kernel-backed single-device chain)
# ---------------------------------------------------------------------------

def test_decode_layer_stream_matches_gather():
    cfg = get_config("smollm-135m").reduced()
    plan = plan_model(cfg, None, (1,), "serve", esl_overlap=False,
                      remat="none", compute_dtype="float32",
                      param_dtype="float32")
    ctx = InitCtx(jax.random.PRNGKey(0), param_dtype=jnp.float32)
    p = init_layer(ctx, cfg, plan, 0)
    a = plan.attn
    B, bs, T = 2, 8, 4
    x = jax.random.normal(jax.random.PRNGKey(1), (B, cfg.d_model))
    pool_k = jax.random.normal(jax.random.PRNGKey(2),
                               (2 * T + 1, a.gp, bs, a.d_head))
    pool_v = jax.random.normal(jax.random.PRNGKey(3),
                               (2 * T + 1, a.gp, bs, a.d_head))
    tables = jnp.asarray(np.arange(1, 2 * T + 1, dtype=np.int32)
                         .reshape(B, T))
    pos = jnp.asarray([5, 11], jnp.int32)
    y_g, c_g = decode_layer(p, x, {"k": pool_k, "v": pool_v}, pos,
                            cfg=cfg, plan=plan, use_kernels=True,
                            block_table=tables, paged_kernel="gather")
    y_s, c_s = decode_layer(p, x, {"k": pool_k, "v": pool_v}, pos,
                            cfg=cfg, plan=plan, use_kernels=True,
                            block_table=tables, paged_kernel="stream")
    np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_g),
                               rtol=1e-4, atol=1e-4)
    # pool updates are identical: the dataflow changes reads, not writes
    np.testing.assert_array_equal(np.asarray(c_s["k"]), np.asarray(c_g["k"]))
    np.testing.assert_array_equal(np.asarray(c_s["v"]), np.asarray(c_g["v"]))


# ---------------------------------------------------------------------------
# engine level: token streams bit-identical across dataflows
# ---------------------------------------------------------------------------

PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [10, 11],
           [3, 1, 4, 1, 5, 9, 2, 6], [2, 7]]


def test_engine_stream_matches_gather_and_dense(tiny_model):
    model, params = tiny_model
    dense = LPUEngine(model, params, slots=3, max_seq=64, paged=False)
    gather = LPUEngine(model, params, slots=3, max_seq=64, paged=True,
                       block_size=16, paged_kernel="gather")
    stream = LPUEngine(model, params, slots=3, max_seq=64, paged=True,
                       block_size=16, paged_kernel="stream")
    od = dense.generate(PROMPTS, max_new_tokens=8)
    og = gather.generate(PROMPTS, max_new_tokens=8)
    os_ = stream.generate(PROMPTS, max_new_tokens=8)
    assert od == og == os_
    # auto resolves to stream for this (block-regular) plan
    auto = LPUEngine(model, params, slots=3, max_seq=64, paged=True,
                     block_size=16)
    assert auto.paged_kernel == "stream"
    assert auto.generate(PROMPTS, max_new_tokens=8) == od


def test_engine_stream_parity_under_preemption(tiny_model):
    """Pool pressure forces recompute preemption with the STREAMED kernel
    selected; the token streams must still match the dense engine."""
    model, params = tiny_model
    dense = LPUEngine(model, params, slots=3, max_seq=64, paged=False)
    od = dense.generate(PROMPTS, max_new_tokens=20)
    stream = LPUEngine(model, params, slots=3, max_seq=64, paged=True,
                       block_size=8, num_blocks=5, paged_kernel="stream")
    os_ = stream.generate(PROMPTS, max_new_tokens=20)
    assert stream.stats.preemptions > 0
    assert od == os_


def test_engine_rejects_bad_kernel_value(tiny_model):
    model, params = tiny_model
    with pytest.raises(ValueError):
        LPUEngine(model, params, slots=2, max_seq=64, paged=True,
                  block_size=16, paged_kernel="bogus")


def test_engine_kv_moved_accounting(tiny_model):
    """The gather oracle materializes the per-request view (read pool +
    write copy + read copy); the streamed kernel only reads tiles."""
    model, params = tiny_model
    kw = dict(slots=3, max_seq=64, paged=True, block_size=16)
    stream = LPUEngine(model, params, paged_kernel="stream", **kw)
    gather = LPUEngine(model, params, paged_kernel="gather", **kw)
    assert stream.kv_bytes_moved_per_step() * 3 == \
        gather.kv_bytes_moved_per_step()
    stream.generate(PROMPTS[:3], max_new_tokens=4)
    assert 0 < stream.stats.peak_pool_blocks <= stream.num_blocks - 1


# ---------------------------------------------------------------------------
# property: the null block (0) never contributes to streamed output
# ---------------------------------------------------------------------------

def _check_null_block_inert(fill: float, len0: int, len1: int) -> None:
    """Scribbling any finite value over block 0 (the null sink absorbing
    padded-prefill and inactive-slot writes) must not change the streamed
    output — valid-length masking happens before the softmax max."""
    q, kp, vp, kn, vn, tables, _ = _fold_inputs(jax.random.PRNGKey(5))
    lengths = jnp.asarray([len0, len1], jnp.int32)
    # tail table entries past the valid length point at the null block
    bs = kp.shape[2]
    t_used0, t_used1 = (len0 + bs - 1) // bs, (len1 + bs - 1) // bs
    tb = np.asarray(tables).copy()
    tb[0, t_used0:] = 0
    tb[1, t_used1:] = 0
    tb = jnp.asarray(tb)
    base = paged_decode_attention(q, kp, vp, tb, lengths,
                                  k_new=kn, v_new=vn)
    kp2 = kp.at[0].set(fill)
    vp2 = vp.at[0].set(fill)
    scribbled = paged_decode_attention(q, kp2, vp2, tb, lengths,
                                       k_new=kn, v_new=vn)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(scribbled))


try:
    from hypothesis import given, settings, strategies as st

    # hypothesis demands bounds exactly representable at width=32
    F32_BIG = float(np.float32(1e30))

    @settings(max_examples=20, deadline=None)
    @given(fill=st.floats(-F32_BIG, F32_BIG, allow_nan=False,
                          allow_infinity=False, width=32),
           len0=st.integers(1, 16), len1=st.integers(1, 16))
    def test_null_block_never_contributes(fill, len0, len1):
        _check_null_block_inert(fill, len0, len1)
except ImportError:        # no hypothesis: fixed adversarial examples
    @pytest.mark.parametrize("fill,len0,len1",
                             [(0.0, 1, 1), (1e30, 3, 16), (-1e30, 16, 2),
                              (-7.5, 8, 9)])
    def test_null_block_never_contributes(fill, len0, len1):
        _check_null_block_inert(fill, len0, len1)


# ---------------------------------------------------------------------------
# ring tp: streamed kernel inside the shard_map engine
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_ring_streamed_engine_matches_dense_tp1():
    """tp=2 shard_map engine with the STREAMED paged kernel (per-rank
    head-sharded pools, replicated tables) must produce bit-identical
    token streams to the tp=1 dense engine."""
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
    prompts = [[1,2,3,4,5,6,7],[8,9,10,11,12],[13,14,15],[16,17,18,19]]
    ref = LPUEngine(m1, p1, slots=3, max_seq=64, paged=False).generate(
        prompts, max_new_tokens=10)
    mesh = make_serving_mesh(tp=2, rings=1)
    eng = LPUEngine(m2, p2, slots=3, max_seq=64, paged=True,
                    block_size=16, mesh=mesh, paged_kernel='stream')
    got = eng.generate(prompts, max_new_tokens=10)
    assert got == ref, (got, ref)
    assert eng.per_rank_kv_bytes() * 2 == eng.kv_cache_bytes()
    print('PASS')
    """, n_devices=2)
    assert "PASS" in out
