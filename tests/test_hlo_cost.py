"""HLO cost parser: must match XLA cost_analysis on unrolled modules and
correctly multiply while-loop (scan) bodies by trip counts."""
import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro.core.hlo_cost import module_cost, xla_cost_analysis


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_unrolled_matches_xla():
    def f(w, x):
        for i in range(8):
            x = x @ w[i]
        return x
    w = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = _compile(f, w, x)
    assert module_cost(c.as_text()).flops == \
        pytest.approx(xla_cost_analysis(c)["flops"], rel=1e-6)


def test_scan_trip_count_multiplied():
    def f(w, x):
        def body(cc, wi):
            return cc @ wi, None
        y, _ = lax.scan(body, x, w)
        return y
    w = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = _compile(f, w, x)
    # XLA counts the body once; parser counts all 8 trips.  XLA's count
    # also includes a few scalar loop-counter flops per trip, so the
    # comparison is approximate at the 1e-5 level.
    assert module_cost(c.as_text()).flops == \
        pytest.approx(8 * xla_cost_analysis(c)["flops"], rel=1e-5)


def test_nested_scan():
    def f(w, x):
        def outer(cc, wi):
            def inner(c2, _):
                return c2 @ wi, None
            c2, _ = lax.scan(inner, cc, None, length=4)
            return c2, None
        y, _ = lax.scan(outer, x, w)
        return y
    w = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = _compile(f, w, x)
    expect = 8 * 4 * 2 * 128 ** 3
    assert module_cost(c.as_text()).flops == pytest.approx(expect, rel=1e-6)


def test_hbm_bytes_scale_with_size():
    def f(x):
        return (x * 2.0).sum()
    small = _compile(f, jax.ShapeDtypeStruct((128, 128), jnp.float32))
    big = _compile(f, jax.ShapeDtypeStruct((512, 512), jnp.float32))
    bs = module_cost(small.as_text()).hbm_bytes
    bb = module_cost(big.as_text()).hbm_bytes
    assert bb > 8 * bs


_POOL_HLO = """\
HloModule jit_window, entry_computation_layout={(bf16[4,9,2,128,64]{3,4,2,1,0})->bf16[4,9,2,128,64]{3,4,2,1,0}}

%fused_scatter (param_0: bf16[4,9,2,128,64], param_1: s32[3,4], param_2: bf16[3,64]) -> bf16[4,9,2,128,64] {
  %param_0 = bf16[4,9,2,128,64]{3,4,2,1,0} parameter(0)
  %copy.9 = bf16[4,9,2,128,64]{4,3,2,1,0} copy(%param_0)
  ROOT %scatter.1 = bf16[4,9,2,128,64]{3,4,2,1,0} scatter(%param_0, %param_1, %param_2), update_window_dims={1}
}

%fused_slice (param_0.1: bf16[4,9,2,128,64], param_1.1: s32[]) -> bf16[1,9,2,128,64] {
  %param_0.1 = bf16[4,9,2,128,64]{3,4,2,1,0} parameter(0)
  ROOT %dynamic-slice.2 = bf16[1,9,2,128,64]{3,4,2,1,0} dynamic-slice(%param_0.1, %param_1.1), dynamic_slice_sizes={1,9,2,128,64}
}

ENTRY %main (pool: bf16[4,9,2,128,64], i: s32[]) -> bf16[4,9,2,128,64] {
  %pool = bf16[4,9,2,128,64]{3,4,2,1,0} parameter(0)
  %i = s32[] parameter(1)
  %copy.1 = bf16[4,9,2,128,64]{3,4,2,1,0} copy(%pool)
  %bitcast.1 = bf16[4,9,2,64,128]{4,3,2,1,0} bitcast(%copy.1)
  %transpose.1 = bf16[4,9,2,64,128]{4,3,2,1,0} transpose(%copy.1), dimensions={0,1,2,4,3}
  %slice_fusion.1 = bf16[1,9,2,128,64]{3,4,2,1,0} fusion(%copy.1, %i), kind=kLoop, calls=%fused_slice
  %copy_bitcast.1 = bf16[9,2,128,64]{3,2,1,0} copy(%slice_fusion.1)
  %scatter_fusion.1 = bf16[4,9,2,128,64]{3,4,2,1,0} fusion(%copy.1, %i), kind=kLoop, calls=%fused_scatter
  ROOT %copy.2 = bf16[4,9,2,128,64]{4,3,2,1,0} copy(%scatter_fusion.1)
}
"""


def test_pool_census_counts_pool_moves():
    """Whole-pool copies (a transposed relayout among them), per-layer
    slices and relayouts, and the layouts of pool-shaped values are
    counted among the module's own ops; in-place scatter fusions,
    bitcasts, parameters and instructions inside fused computations
    are not."""
    from repro.core.hlo import pool_census
    leaf = jax.ShapeDtypeStruct((4, 9, 2, 128, 64), jnp.bfloat16)
    c = pool_census(_POOL_HLO, leaf)
    assert c.copies == ["copy.1", "transpose.1", "copy.2"]
    assert c.per_layer == ["slice_fusion.1", "copy_bitcast.1"]
    assert sorted(set(c.layouts)) == ["3,4,2,1,0", "4,3,2,1,0"]
    assert c.row() == {"whole_pool_copies": 3, "per_layer_pool_ops": 2,
                       "pool_layouts": ["3,4,2,1,0", "4,3,2,1,0"]}
    # another dtype's leaf of the same shape is not this pool
    other = jax.ShapeDtypeStruct(leaf.shape, jnp.int8)
    assert pool_census(_POOL_HLO, other).row()["whole_pool_copies"] == 0
