"""Fixed-point substrate: bit-level invariants (unit + hypothesis)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import example, given, settings, st

from repro.core.fixedpoint import (EXP_FRAC, I32, IN_FRAC, IN_MAX, IN_MIN,
                                   T_FRAC, dequantize, floor_log2,
                                   mantissa_frac, quantize, sat_rshift)


def test_quantize_range_saturates():
    q = quantize(jnp.asarray([1e9, -1e9, 0.0]))
    assert int(q[0]) == IN_MAX and int(q[1]) == IN_MIN and int(q[2]) == 0


def test_quantize_dequantize_grid():
    # every representable S5.10 value roundtrips exactly
    grid = np.arange(IN_MIN, IN_MAX + 1, 7, dtype=np.int32)
    x = grid.astype(np.float32) / (1 << IN_FRAC)
    q = quantize(jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(q), grid)
    np.testing.assert_allclose(np.asarray(dequantize(q)), x, atol=0)


@given(st.floats(-31.9, 31.9))
@settings(max_examples=200, deadline=None)
@example(8.717285514986123)   # the bound ignores the input's f32 cast
def test_quantize_error_bound(x):
    err = abs(float(dequantize(quantize(jnp.asarray(x)))) - x)
    assert err <= 0.5 / (1 << IN_FRAC) + 1e-7


@given(st.integers(1, 2**31 - 1))
@settings(max_examples=300, deadline=None)
def test_floor_log2_bitexact(v):
    assert int(floor_log2(jnp.asarray(v, jnp.int32))) == v.bit_length() - 1


@given(st.integers(1, 2**30))
@settings(max_examples=200, deadline=None)
def test_mantissa_frac_reconstructs(v):
    e = v.bit_length() - 1
    frac = int(mantissa_frac(jnp.asarray(v, jnp.int32),
                             jnp.asarray(e, jnp.int32)))
    # frac/2^T_FRAC ~ v/2^e - 1 within shift truncation
    approx = (1 + frac / (1 << T_FRAC)) * (1 << e)
    assert abs(approx - v) <= max(1.0, v / (1 << T_FRAC) * 2)


def test_sat_rshift_clamps():
    x = jnp.asarray([1 << 20], jnp.int32)
    assert int(sat_rshift(x, jnp.asarray([40]))[0]) == 0       # clamp at 31
    assert int(sat_rshift(x, jnp.asarray([-5]))[0]) == 1 << 20  # clamp at 0
