"""Quantized KV pool + int8 weight streaming.

Covers the tentpole's three contracts:

* quantization math — symmetric absmax per (row, kv head), all-zero
  rows (the null block) dequantize to EXACT zeros, outlier rows stay
  finite and within the rounding bound, fp8 storage when the jax build
  provides it;
* kernel dequant parity — the streamed Pallas kernel dequantizing
  inside its tile loop agrees with the gather oracle and with a
  hand-dequantized dense reference, including the folded new token;
* scale survival — the fp16 scale side-arrays ride along through every
  pool lifecycle event (copy-on-write duplication, prefix-cache block
  sharing, speculative rollback, preemption + recomputation), proven by
  bit-identical greedy streams across each on/off pair on the SAME
  int8 engine;

plus the int8 weight-streaming gemv (per-output-column scales applied
at the f32 flush) and the per-operand VMEM sizing fix in plan_blocks.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compiler.mapper import plan_model
from repro.compiler.plan import resolve_kv_precision
from repro.configs import get_config
from repro.kernels.decode_attention import (decode_attention_ref,
                                            gather_kv_pages,
                                            paged_decode_attention)
from repro.kernels.gemv import gemv, gemv_ref, plan_blocks, quantize_weight
from repro.kernels.gemv.ops import VMEM_BYTES
from repro.models.registry import build_model
from repro.serving.config import EngineConfig
from repro.serving.engine import LPUEngine
from repro.serving.kv_cache import (cache_bytes, copy_pool_block,
                                    dequantize_kv, per_rank_block_bytes,
                                    qmax_for_dtype, quantize_kv_rows)

HAS_FP8 = hasattr(jnp, "float8_e4m3fn")


# ---------------------------------------------------------------------------
# quantization math
# ---------------------------------------------------------------------------

def test_qmax_for_dtype():
    assert qmax_for_dtype(jnp.int8) == 127.0
    if HAS_FP8:
        assert qmax_for_dtype(jnp.float8_e4m3fn) == 448.0
    with pytest.raises(ValueError):
        qmax_for_dtype(jnp.float16)


def test_int8_roundtrip_within_rounding_bound():
    rows = jax.random.normal(jax.random.PRNGKey(0), (5, 16, 2, 32))
    q, s = quantize_kv_rows(rows, jnp.int8, jnp.float16)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float16
    assert s.shape == rows.shape[:-1]
    deq = dequantize_kv(q, s)
    # per-element error <= half a quantization step plus the fp16 scale
    # rounding amplified by up to qmax: (0.5 + 127 * 2^-11) * scale
    bound = np.asarray(s, np.float32)[..., None] * 0.57 + 1e-6
    assert np.all(np.abs(np.asarray(deq - rows)) <= bound)


def test_all_zero_rows_dequantize_to_exact_zeros():
    """The null block's contract: scale 0, no NaN from the 0/0 divisor,
    and the dequantized row is EXACTLY zero (so the null block never
    contributes to attention)."""
    rows = jnp.zeros((3, 8, 2, 32))
    q, s = quantize_kv_rows(rows, jnp.int8, jnp.float16)
    assert not np.any(np.isnan(np.asarray(s)))
    assert np.all(np.asarray(s) == 0.0)
    assert np.all(np.asarray(q) == 0)
    assert np.all(np.asarray(dequantize_kv(q, s)) == 0.0)


def test_outlier_row_stays_finite_and_bounded():
    """One huge magnitude sets the row scale; small entries may collapse
    to zero but nothing overflows, and every element stays within half a
    step of its source."""
    rows = np.full((1, 4, 1, 32), 0.3, np.float32)
    rows[0, 1, 0, 7] = 1e4
    q, s = quantize_kv_rows(jnp.asarray(rows), jnp.int8, jnp.float16)
    deq = np.asarray(dequantize_kv(q, s))
    assert np.all(np.isfinite(deq))
    bound = np.asarray(s, np.float32)[..., None] * 0.57 + 1e-6
    assert np.all(np.abs(deq - rows) <= bound)
    # the outlier itself survives to within a (rounding + fp16-scale)
    # step of its source
    assert abs(deq[0, 1, 0, 7] - 1e4) <= 0.57 * float(s[0, 1, 0])


@pytest.mark.skipif(not HAS_FP8, reason="no jnp.float8_e4m3fn")
def test_fp8_roundtrip():
    rows = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 2, 32))
    q, s = quantize_kv_rows(rows, jnp.float8_e4m3fn, jnp.float16)
    assert q.dtype == jnp.float8_e4m3fn
    deq = np.asarray(dequantize_kv(q, s))
    # fp8 e4m3 keeps ~2 significand bits through the scale: coarse but
    # proportional error
    ref = np.asarray(rows)
    assert np.abs(deq - ref).max() <= 0.1 * np.abs(ref).max() + 1e-3


def test_resolve_kv_precision_sizing():
    p = resolve_kv_precision("int8", "float32")
    assert p.quantized and p.store_dtype == "int8"
    assert p.itemsize == 1 and p.scale_itemsize == 2
    # the byte count the 0.55x moved-bytes gate depends on:
    # d_head + scale vs 2 * d_head
    assert p.bytes_per_row_head(32) == 34
    auto = resolve_kv_precision("auto", "float32")
    assert not auto.quantized and auto.scale_itemsize == 0
    assert auto.bytes_per_row_head(32) == 128
    fp16 = resolve_kv_precision("fp16", "float32")
    assert not fp16.quantized and fp16.bytes_per_row_head(32) == 64


def test_per_rank_block_bytes_includes_scales():
    base = per_rank_block_bytes(2, 2, 32, 16, 1)
    with_scales = per_rank_block_bytes(2, 2, 32, 16, 1, scale_bytes=2)
    assert with_scales - base == 2 * 2 * 16 * 2 * 2  # 2KV*L*bs*G*scale


# ---------------------------------------------------------------------------
# kernel dequant parity (stream vs oracle vs hand-dequantized dense)
# ---------------------------------------------------------------------------

def _quantized_fold_inputs(key, B=2, H=4, G=2, dh=16, bs=8, T=4, N=9):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (B, H, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (N, G, bs, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (N, G, bs, dh), jnp.float32)
    kq, ksc = quantize_kv_rows(kp, jnp.int8, jnp.float16)
    vq, vsc = quantize_kv_rows(vp, jnp.int8, jnp.float16)
    k_new = jax.random.normal(ks[3], (B, G, dh), jnp.float32)
    v_new = jax.random.normal(ks[4], (B, G, dh), jnp.float32)
    tables = jnp.asarray(np.arange(1, B * T + 1, dtype=np.int32)
                         .reshape(B, T))
    lengths = jnp.asarray([13, 27], jnp.int32)
    return q, kq, vq, ksc, vsc, k_new, v_new, tables, lengths


def test_stream_dequant_matches_hand_dequantized_dense():
    """The Pallas kernel dequantizing per tile == dequantize the whole
    pool first and run the dense reference."""
    (q, kq, vq, ksc, vsc, kn, vn, tables,
     lengths) = _quantized_fold_inputs(jax.random.PRNGKey(2))
    B, H = q.shape[:2]
    gs = H // kq.shape[1]
    out = paged_decode_attention(q, kq, vq, tables, lengths,
                                 k_new=kn, v_new=vn,
                                 k_scale=ksc, v_scale=vsc)
    kd, vd = dequantize_kv(kq, ksc), dequantize_kv(vq, vsc)
    ke = jnp.repeat(gather_kv_pages(kd, tables), gs, axis=2)
    ve = jnp.repeat(gather_kv_pages(vd, tables), gs, axis=2)
    ke = ke.at[jnp.arange(B), lengths].set(jnp.repeat(kn, gs, axis=1))
    ve = ve.at[jnp.arange(B), lengths].set(jnp.repeat(vn, gs, axis=1))
    ref = decode_attention_ref(q, ke, ve, lengths + 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_stream_dequant_matches_gather_oracle():
    """Both paged paths must dequantize identically (the use_pallas=False
    oracle is what the engine's gather mode runs)."""
    (q, kq, vq, ksc, vsc, kn, vn, tables,
     lengths) = _quantized_fold_inputs(jax.random.PRNGKey(3))
    pal = paged_decode_attention(q, kq, vq, tables, lengths,
                                 k_new=kn, v_new=vn,
                                 k_scale=ksc, v_scale=vsc)
    ora = paged_decode_attention(q, kq, vq, tables, lengths,
                                 k_new=kn, v_new=vn,
                                 k_scale=ksc, v_scale=vsc,
                                 use_pallas=False)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ora),
                               rtol=2e-5, atol=2e-5)


def test_copy_pool_block_carries_scales():
    """Copy-on-write duplicates the scale side-arrays with the data —
    a CoW that forgot the scales would dequantize the copy wrongly."""
    key = jax.random.PRNGKey(4)
    kp = jax.random.normal(key, (1, 5, 2, 4, 8))   # (n_sb, N, G, bs, dh)
    kq, ksc = quantize_kv_rows(kp, jnp.int8, jnp.float16)
    cache = {"l0": {"k": kq, "v": kq, "k_scale": ksc, "v_scale": ksc}}
    out = copy_pool_block(cache, jnp.int32(2), jnp.int32(4))
    for leaf in ("k", "v", "k_scale", "v_scale"):
        np.testing.assert_array_equal(
            np.asarray(out["l0"][leaf][:, 4]),
            np.asarray(cache["l0"][leaf][:, 2]))


# ---------------------------------------------------------------------------
# engine level: scale survival + accuracy
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


PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [10, 11]]


def _int8(model, params, **kw):
    base = dict(slots=2, max_seq=64, paged=True, block_size=16,
                kv_dtype="int8")
    base.update(kw)
    return LPUEngine(model, params, EngineConfig(**base))


def test_engine_int8_stream_matches_gather(tiny_model):
    """Both dataflows fold the SAME quantize->dequantize round-trip of
    the new token, so their greedy streams agree token-for-token."""
    model, params = tiny_model
    outs = {}
    for kern in ("stream", "gather"):
        eng = _int8(model, params, paged_kernel=kern)
        outs[kern] = eng.generate(PROMPTS, max_new_tokens=8)
    assert outs["stream"] == outs["gather"]


def test_engine_int8_cache_has_scale_leaves_and_honest_bytes(tiny_model):
    model, params = tiny_model
    eng = _int8(model, params)
    l0 = eng.cache["l0"]
    assert set(l0) == {"k", "v", "k_scale", "v_scale"}
    assert l0["k"].dtype == jnp.int8
    assert l0["k_scale"].dtype == jnp.bfloat16
    assert l0["k_scale"].shape == l0["k"].shape[:-1]
    # reported bytes include the scale side-arrays (per-rank block
    # bytes x blocks is exactly the pool pytree's footprint)
    a = eng.plan.attn
    per_block = per_rank_block_bytes(eng.cfg.n_layers, a.kv_per_rank,
                                     a.d_head, eng.block_size,
                                     eng.kv_prec.itemsize,
                                     eng.kv_prec.scale_itemsize)
    assert cache_bytes(eng.cache) == per_block * eng.num_blocks
    assert eng.kv_cache_bytes() == cache_bytes(eng.cache)


def test_engine_int8_prefix_sharing_parity(tiny_model):
    """Shared-prefix admissions map quantized blocks (and their scales)
    into other tables; streams must match the cold-start engine."""
    model, params = tiny_model
    sys_prompt = [7, 3, 5, 2, 9, 4, 8, 6] * 4       # 2 full blocks
    prompts = [sys_prompt + [t] for t in (11, 12, 13)]
    outs = {}
    for on in (False, True):
        eng = _int8(model, params, slots=3, prefix_cache=on)
        outs[on] = eng.generate(prompts, max_new_tokens=8)
        if on:
            assert eng.stats.prefix_hit_blocks > 0
    assert outs[True] == outs[False]


def test_engine_int8_speculative_parity(tiny_model):
    """Rejection sampling stays EXACT on the quantized pool: draft,
    verify and rollback all read/write the same stored (int8, scale)
    pairs, so spec-on streams match spec-off bit-for-bit."""
    model, params = tiny_model
    motif = [3, 1, 4, 1]
    prompts = [motif * 6, motif * 5]
    outs = {}
    for spec in ("off", "ngram"):
        eng = _int8(model, params, max_seq=128, speculate=spec,
                    draft_k=4)
        outs[spec] = eng.generate(prompts, max_new_tokens=12)
        if spec == "ngram":
            assert eng.stats.accepted_tokens > 0
    assert outs["ngram"] == outs["off"]


def test_engine_int8_preemption_parity(tiny_model):
    """A pool too small for all streams forces preempt + recompute; the
    recomputed blocks requantize to the same stored values, so streams
    match the uncontended engine."""
    model, params = tiny_model
    big = _int8(model, params, slots=3)
    ob = big.generate(PROMPTS, max_new_tokens=20)
    # 3 slots x up to 24 resident tokens but only 4 usable 8-tok blocks:
    # streams evict each other and recompute on resume
    small = _int8(model, params, slots=3, block_size=8, num_blocks=5)
    os_ = small.generate(PROMPTS, max_new_tokens=20)
    assert small.stats.preemptions > 0
    assert os_ == ob


def test_engine_int8_greedy_drift_bound(tiny_model):
    """Accuracy gate at engine level: int8 streams stay within the
    documented common-prefix bound of the full-precision engine (the
    same bound serving_bench enforces against its fp16 row)."""
    model, params = tiny_model
    fp = LPUEngine(model, params, EngineConfig(slots=2, max_seq=64,
                                               paged=True, block_size=16))
    of = fp.generate(PROMPTS, max_new_tokens=8)
    oq = _int8(model, params).generate(PROMPTS, max_new_tokens=8)
    agree = []
    for a, b in zip(oq, of):
        n = min(len(a), len(b))
        k = 0
        while k < n and a[k] == b[k]:
            k += 1
        agree.append(k / n)
    assert sum(agree) / len(agree) >= 0.75, agree


def test_engine_fp16_pool_halves_bytes(tiny_model):
    """An explicit fp dtype restores the pool at that width — no scale
    arrays, half the f32 bytes."""
    model, params = tiny_model
    f32 = LPUEngine(model, params, EngineConfig(slots=2, max_seq=64,
                                                paged=True, block_size=16))
    f16 = LPUEngine(model, params, EngineConfig(slots=2, max_seq=64,
                                                paged=True, block_size=16,
                                                kv_dtype="float16"))
    assert "k_scale" not in f16.cache["l0"]
    assert f16.kv_cache_bytes() * 2 == f32.kv_cache_bytes()


def test_engine_int8_moved_bytes_ratio(tiny_model):
    """The analytic bandwidth claim the bench gates: int8+scales move
    (dh + 2) / (2 * dh) of the fp16 bytes per step — 0.531 at dh=32,
    inside the 0.55 CI gate."""
    model, params = tiny_model
    f16 = LPUEngine(model, params, EngineConfig(slots=2, max_seq=64,
                                                paged=True, block_size=16,
                                                kv_dtype="float16"))
    q8 = _int8(model, params)
    ratio = q8.kv_bytes_moved_per_step() / f16.kv_bytes_moved_per_step()
    assert ratio <= 0.55, ratio


def test_engine_int8_requires_paged(tiny_model):
    model, params = tiny_model
    with pytest.raises(ValueError, match="paged"):
        LPUEngine(model, params, EngineConfig(paged=False,
                                              kv_dtype="int8"))


# ---------------------------------------------------------------------------
# int8 weight streaming (gemv) + per-operand VMEM sizing
# ---------------------------------------------------------------------------

def test_gemv_int8_matches_fp_within_quant_error():
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (2, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(6), (256, 256), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(7), (256,), jnp.float32)
    qw, ws = quantize_weight(w)
    assert qw.dtype == jnp.int8 and ws.shape == (256,)
    out = gemv(x, qw, b, w_scale=ws)
    ref = x @ w + b
    rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
    assert rel <= 0.05, rel


def test_gemv_int8_pallas_matches_ref_exactly():
    """Same quantized operands through the kernel and the jnp oracle:
    the scale is applied at the f32 flush BEFORE the bias in both."""
    x = jax.random.normal(jax.random.PRNGKey(8), (3, 128), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(9), (128, 128), jnp.float32)
    b = jnp.ones((128,), jnp.float32) * 100.0      # bias must NOT scale
    qw, ws = quantize_weight(w)
    pal = gemv(x, qw, b, w_scale=ws, use_pallas=True)
    ref = gemv_ref(x, qw, b, w_scale=ws)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=2e-5, atol=2e-4)


def test_quantize_weight_zero_column():
    w = jnp.zeros((64, 4))
    qw, ws = quantize_weight(w)
    assert np.all(np.asarray(ws) == 0) and np.all(np.asarray(qw) == 0)
    out = gemv_ref(jnp.ones((1, 64)), qw, None, w_scale=ws)
    assert np.all(np.asarray(out) == 0)


def test_plan_blocks_sizes_per_operand():
    """int8 weights with f32 activations: the streamed tile is budgeted
    at 1 B/elem while the stationary activation pays its own 4 B/elem —
    a uniform byte width would either starve or overflow the window."""
    B, K, N = 8, 4096, 4096
    budget = VMEM_BYTES // 2
    bk, bn = plan_blocks(B, K, N, w_bytes=1, x_bytes=4)
    assert 2 * bk * bn * 1 + B * bk * 4 + B * bn * 4 <= budget
    # the int8 stream affords at least the fp16 tile area
    bk2, bn2 = plan_blocks(B, K, N, w_bytes=2, x_bytes=4)
    assert bk * bn >= bk2 * bn2
    assert 2 * bk2 * bn2 * 2 + B * bk2 * 4 + B * bn2 * 4 <= budget
