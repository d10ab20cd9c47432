"""Ahead-of-time compiles of the paged decode kernel for a described TPU
v5e chip, at the widths of the served models.

Interpret mode cannot see what Mosaic refuses (block shapes that are
neither (8, 128)-aligned nor span their array, loads of the wrong vector
type); the TPU compiler is installed here and compiles for a chip that
is described, not attached.  Every call site of the served path is
compiled — decode with the new-token fold, chunk-as-batch prefill,
speculative verify, and the int8 / fp8 pools — and each compiled module
must hold the Pallas kernel (``tpu_custom_call``), not an XLA stand-in.
The engine's own one-step decode window is compiled too, and held to
reading and writing its stacked KV pool in place.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library at a time, and under
pytest-xdist every worker imports this file.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.compiler.mapper import plan_model
from repro.configs import get_config
from repro.core.hlo import pool_census
from repro.kernels.decode_attention.ops import paged_decode_attention
from repro.models.registry import build_model
from repro.serving.config import EngineConfig
from repro.serving.engine import LPUEngine

SLOTS, MAX_SEQ, BLOCK = 16, 1024, 128
T = MAX_SEQ // BLOCK
N = SLOTS * T + 1                       # dense-equivalent pool + null block


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                          # pragma: no cover
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache, so keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _widths(arch):
    cfg = get_config(arch)
    return cfg.n_heads, cfg.n_kv_heads, cfg.d_head


def _compile(one_chip, B, arch, *, fold, per_query_tables=True,
             store=jnp.bfloat16):
    H, G, dh = _widths(arch)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    args = [sds((B, H, dh), jnp.bfloat16), sds((N, G, BLOCK, dh), store),
            sds((N, G, BLOCK, dh), store), sds((B, T), jnp.int32),
            sds((B,), jnp.int32)]
    kw = {}
    if fold:
        kw["k_new"] = sds((B, G, dh), jnp.bfloat16)
        kw["v_new"] = sds((B, G, dh), jnp.bfloat16)
    if store in (jnp.int8, jnp.float8_e4m3fn):
        # bf16 scales, as resolve_kv_precision stores them
        kw["k_scale"] = sds((N, G, BLOCK), jnp.bfloat16)
        kw["v_scale"] = sds((N, G, BLOCK), jnp.bfloat16)
    compiled = paged_decode_attention.lower(*args, interpret=False,
                                            **kw).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, f"{arch}: no Pallas kernel compiled"
    return compiled


ARCHS = ["opt-1.3b", "qwen1.5-4b"]


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_with_fold_compiles(one_chip, arch):
    _compile(one_chip, SLOTS, arch, fold=True)


@pytest.mark.parametrize("arch", ARCHS)
def test_chunk_prefill_compiles(one_chip, arch):
    # a 64-token chunk rides as 64 single-token queries over one table
    _compile(one_chip, 64, arch, fold=False)


@pytest.mark.parametrize("arch", ARCHS)
def test_verify_compiles(one_chip, arch):
    # every slot's last token + 4 drafts, each query with its own table
    _compile(one_chip, SLOTS * 5, arch, fold=False)


@pytest.mark.parametrize("store", [jnp.int8, jnp.float8_e4m3fn])
@pytest.mark.parametrize("arch", ARCHS)
def test_quantized_pool_compiles(one_chip, arch, store):
    _compile(one_chip, SLOTS, arch, fold=True, store=store)


def test_fp16_pool_is_refused(one_chip):
    """The v5e compiler has no fp16 vector loads; this is the refusal the
    engine turns into a construction-time error for kv_dtype='float16'
    (bf16 pools and the int8/fp8 pools' bf16 scales compile above)."""
    with pytest.raises(Exception, match="Invalid vector type"):
        _compile(one_chip, SLOTS, "opt-1.3b", fold=True, store=jnp.float16)


@pytest.mark.parametrize("arch,slots", [("opt-1.3b", 16), ("opt-6.7b", 8)])
def test_decode_window_keeps_pool_in_place(one_chip, monkeypatch, arch,
                                           slots):
    """The engine's one-step decode window, compiled for the chip, reads
    every layer's KV straight from the stacked pool and writes the new
    rows into it in the layout the kernel reads: no per-layer slice or
    relayout of the pool, and no whole-pool copy beyond the one that
    takes each undonated leaf (K, V) into the layer loop.  d_head 64
    (OPT-1.3B) and 128 (OPT-6.7B) are stored in different layouts, so
    both widths are compiled.  Six layers make a pool leaf larger than
    the chip's VMEM, as a served model's is."""
    kern = sys.modules["repro.kernels.decode_attention.decode_attention"]
    monkeypatch.setattr(kern, "interpret_mode", lambda interpret=None: False)
    jax.clear_caches()
    cfg = dataclasses.replace(get_config(arch), n_layers=6)
    plan = plan_model(cfg, None, (1,), "serve", esl_overlap=False,
                      remat="none")
    model = build_model(cfg, plan)
    params = jax.eval_shape(lambda k: model.init(k)[0],
                            jax.random.PRNGKey(0))
    eng = LPUEngine(model, params, EngineConfig(
        slots=slots, max_seq=512, block_size=BLOCK, paged_kernel="stream"))
    compiled = eng.lower_decode(one_chip).compile()
    jax.clear_caches()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, f"{arch}: no Pallas kernel compiled"
    leaf = eng.cache["l0"]["k"]
    census = pool_census(text, leaf)
    assert census.per_layer == [], census.row()
    assert len(census.copies) <= 2, census.row()
    assert len(set(census.layouts)) == 1, census.row()
    assert compiled.memory_analysis().temp_size_in_bytes < leaf.nbytes
