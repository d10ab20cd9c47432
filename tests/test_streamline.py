"""Streamlined (kernel-backed) decode layer == ref-path decode layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compiler.mapper import plan_model
from repro.configs import get_config
from repro.core.streamline import decode_layer, stream_bytes_per_layer
from repro.models.common import InitCtx
from repro.models.transformer import init_layer


@pytest.mark.parametrize("use_kernels", [False, True])
def test_decode_layer_kernel_parity(use_kernels):
    cfg = get_config("deepseek-coder-33b").reduced()
    plan = plan_model(cfg, None, (1,), "serve", esl_overlap=False,
                      remat="none", compute_dtype="float32",
                      param_dtype="float32")
    ctx = InitCtx(jax.random.PRNGKey(0), param_dtype=jnp.float32)
    p = init_layer(ctx, cfg, plan, 0)
    B, S = 2, 32
    a = plan.attn
    x = jax.random.normal(jax.random.PRNGKey(1), (B, cfg.d_model))
    cache = {"k": jnp.zeros((B, S, a.gp, a.d_head)),
             "v": jnp.zeros((B, S, a.gp, a.d_head))}
    pos = jnp.asarray([3, 7], jnp.int32)
    y, c2 = decode_layer(p, x, cache, pos, cfg=cfg, plan=plan,
                         use_kernels=use_kernels)
    y_ref, c_ref = decode_layer(p, x, cache, pos, cfg=cfg, plan=plan,
                                use_kernels=False)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(c2["k"]), np.asarray(c_ref["k"]),
                               rtol=1e-5, atol=1e-5)
    assert y.shape == (B, cfg.d_model)


def test_decode_layer_paged_matches_dense():
    """decode_layer against the shared block pool (block tables) must
    equal the dense per-slot cache path bit-for-bit: the table only
    redirects where KV tiles live, never what is computed."""
    cfg = get_config("smollm-135m").reduced()
    plan = plan_model(cfg, None, (1,), "serve", esl_overlap=False,
                      remat="none", compute_dtype="float32",
                      param_dtype="float32")
    ctx = InitCtx(jax.random.PRNGKey(0), param_dtype=jnp.float32)
    p = init_layer(ctx, cfg, plan, 0)
    a = plan.attn
    B, S, bs = 2, 32, 8
    T = S // bs
    x = jax.random.normal(jax.random.PRNGKey(1), (B, cfg.d_model))
    k0 = jax.random.normal(jax.random.PRNGKey(2), (B, S, a.gp, a.d_head))
    v0 = jax.random.normal(jax.random.PRNGKey(3), (B, S, a.gp, a.d_head))
    pos = jnp.asarray([5, 11], jnp.int32)
    y_ref, c_ref = decode_layer(p, x, {"k": k0, "v": v0}, pos, cfg=cfg,
                                plan=plan, use_kernels=False)
    # scatter the dense cache into a pool through per-request tables
    # (block 0 = null block, requests own disjoint blocks 1..2T)
    tables = np.arange(1, 2 * T + 1, dtype=np.int32).reshape(B, T)
    pool_k = jnp.zeros((2 * T + 1, a.gp, bs, a.d_head))
    pool_v = jnp.zeros((2 * T + 1, a.gp, bs, a.d_head))
    # seq-major rows -> the pool's head-major (gp, bs, dh) blocks
    chunks_k = np.asarray(k0).reshape(B, T, bs, a.gp,
                                      a.d_head).swapaxes(2, 3)
    chunks_v = np.asarray(v0).reshape(B, T, bs, a.gp,
                                      a.d_head).swapaxes(2, 3)
    pool_k = pool_k.at[tables].set(chunks_k)
    pool_v = pool_v.at[tables].set(chunks_v)
    y_pg, c_pg = decode_layer(p, x, {"k": pool_k, "v": pool_v}, pos,
                              cfg=cfg, plan=plan, use_kernels=False,
                              block_table=jnp.asarray(tables))
    assert np.array_equal(np.asarray(y_pg), np.asarray(y_ref))
    # the new token's KV landed in the right physical block slot
    for b in range(B):
        blk, off = tables[b, int(pos[b]) // bs], int(pos[b]) % bs
        assert np.array_equal(np.asarray(c_pg["k"][blk, :, off]),
                              np.asarray(c_ref["k"][b, int(pos[b])]))


def test_stream_bytes_accounting():
    cfg = get_config("deepseek-coder-33b")
    plan = plan_model(cfg, ("data", "model"), (16, 16), "serve")
    per_layer = stream_bytes_per_layer(cfg, plan, kv_len=1024)
    # weights dominate: roughly layer params * 2B / tp (padding inflates)
    approx = cfg.layer_params(0) * 2 / 16
    assert 0.8 * approx < per_layer < 2.5 * approx
