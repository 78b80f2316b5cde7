"""Property tests for the error-free transformations.

two_sum is exact; two_prod is near-exact with the documented bound (the
contraction-robust variant trades bit-exactness for immunity to XLA:CPU's
fma contraction — see efts.py docstring).  Every bound is checked against
``fractions.Fraction`` oracles, both eagerly and under jit *in fused
broadcast contexts* (the exact setting where the naive Dekker formulation
was observed to collapse).
"""

from fractions import Fraction

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(pip install -r requirements-dev.txt)")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import efts

# XLA CPU flushes subnormals to zero (FTZ), and EFT error terms of products of
# tiny normals are themselves subnormal — so EFT guarantees hold on the normal
# range only.  Constrain magnitudes well inside it (documented in efts.py).
finite64 = st.floats(
    allow_nan=False, allow_infinity=False, min_value=-1e150, max_value=1e150
).filter(lambda x: x == 0.0 or abs(x) > 1e-120)
finite32 = st.floats(
    allow_nan=False, allow_infinity=False, min_value=-2.0**50, max_value=2.0**50, width=32
).filter(lambda x: x == 0.0 or abs(x) > 1e-12)


def _frac(x) -> Fraction:
    return Fraction(float(x))


@settings(max_examples=200, deadline=None)
@given(finite64, finite64)
def test_two_sum_exact_f64(a, b):
    s, e = efts.two_sum(jnp.float64(a), jnp.float64(b))
    assert _frac(s) + _frac(e) == _frac(a) + _frac(b)
    assert float(s) == a + b  # s is the correctly rounded sum


@settings(max_examples=200, deadline=None)
@given(finite32, finite32)
def test_two_sum_exact_f32(a, b):
    a32, b32 = np.float32(a), np.float32(b)
    s, e = efts.two_sum(jnp.float32(a32), jnp.float32(b32))
    assert _frac(s) + _frac(e) == _frac(a32) + _frac(b32)


@settings(max_examples=200, deadline=None)
@given(finite64, finite64)
def test_two_prod_bound_f64(a, b):
    p, e = efts.two_prod(jnp.float64(a), jnp.float64(b))
    got = _frac(p) + _frac(e)
    want = _frac(a) * _frac(b)
    tol = efts.TWO_PROD_RELERR[jnp.dtype(jnp.float64)]
    assert abs(float(got - want)) <= tol * abs(float(want)) or want == 0
    # p is within 1 ulp of the rounded product
    assert abs(float(p) - a * b) <= abs(a * b) * 2.0**-52


@settings(max_examples=200, deadline=None)
@given(finite32, finite32)
def test_two_prod_bound_f32(a, b):
    a32, b32 = np.float32(a), np.float32(b)
    p, e = efts.two_prod(jnp.float32(a32), jnp.float32(b32))
    got = _frac(p) + _frac(e)
    want = _frac(a32) * _frac(b32)
    tol = efts.TWO_PROD_RELERR[jnp.dtype(jnp.float32)]
    assert abs(float(got - want)) <= tol * abs(float(want)) or want == 0


def test_two_prod_f32_is_exact():
    # with 12/12-bit splits all four partials are exact in f32, so the only
    # error source is the e1+(e2+e3) fold; on random data it is usually exact
    rng = np.random.default_rng(0)
    bad = 0
    for _ in range(200):
        a, b = np.float32(rng.standard_normal()), np.float32(rng.standard_normal())
        p, e = efts.two_prod(jnp.float32(a), jnp.float32(b))
        if _frac(p) + _frac(e) != _frac(a) * _frac(b):
            bad += 1
    assert bad <= 5  # rare e-fold rounding only


@settings(max_examples=100, deadline=None)
@given(finite64, finite64)
def test_quick_two_sum_exact_when_ordered(a, b):
    hi, lo = (a, b) if abs(a) >= abs(b) else (b, a)
    s, e = efts.quick_two_sum(jnp.float64(hi), jnp.float64(lo))
    assert _frac(s) + _frac(e) == _frac(hi) + _frac(lo)


@settings(max_examples=100, deadline=None)
@given(finite64)
def test_mask_split_exact(a):
    """efts.split is error-free and its halves multiply exactly."""
    hi, lo = efts.split(jnp.float64(a))
    assert _frac(hi) + _frac(lo) == _frac(a)
    # hi has at most 26 significant bits -> hi * hi is exact in f64
    assert _frac(float(hi) * float(hi)) == _frac(hi) * _frac(hi)


@settings(max_examples=100, deadline=None)
@given(finite32)
def test_mask_split_exact_f32(a):
    """efts.split over f32: error-free, cross products exact."""
    a32 = np.float32(a)
    hi, lo = efts.split(jnp.float32(a32))
    assert _frac(hi) + _frac(lo) == _frac(a32)
    # 12-bit halves: all cross products exact in f32
    assert _frac(np.float32(float(hi)) * np.float32(float(lo))) == _frac(hi) * _frac(lo)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_two_prod_jit_fused_broadcast(dtype):
    """Regression: the setting where fma contraction broke Dekker two_prod.

    jit-compile a fused broadcast (8,1)x(1,8) two_prod and verify the bound
    elementwise against Fraction — this fails for Dekker's formulation,
    which subtracts the rounded product from the partial products.
    """
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.standard_normal((8, 1)), dtype)
    b = jnp.asarray(rng.standard_normal((1, 8)), dtype)
    p, e = jax.jit(efts.two_prod)(a, b)
    tol = efts.TWO_PROD_RELERR[jnp.dtype(dtype)]
    for i in range(8):
        for j in range(8):
            got = _frac(p[i, j]) + _frac(e[i, j])
            want = _frac(a[i, 0]) * _frac(b[0, j])
            assert abs(float(got - want)) <= tol * abs(float(want))


def test_two_sum_jit_fused_broadcast():
    rng = np.random.default_rng(4)
    a = jnp.asarray(rng.standard_normal((8, 1)))
    b = jnp.asarray(rng.standard_normal((1, 8)) * 1e-12)
    s, e = jax.jit(efts.two_sum)(a, b)
    for i in range(8):
        for j in range(8):
            assert _frac(s[i, j]) + _frac(e[i, j]) == _frac(a[i, 0]) + _frac(b[0, j])


@settings(max_examples=200, deadline=None)
@given(finite64, finite64)
def test_two_prod_exact_f64(a, b):
    p, e = efts.two_prod_exact(jnp.float64(a), jnp.float64(b))
    assert _frac(p) + _frac(e) == _frac(a) * _frac(b)


@settings(max_examples=200, deadline=None)
@given(finite64, finite64)
def test_two_prod_terms_sum_exactly(a, b):
    terms = efts.two_prod_terms(jnp.float64(a), jnp.float64(b))
    assert sum((_frac(t) for t in terms), Fraction(0)) == _frac(a) * _frac(b)


def test_two_sum_vectorized():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((64, 3))
    b = rng.standard_normal((64, 3)) * 1e-12
    s, e = efts.two_sum(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(s) + np.asarray(e), a + b)
