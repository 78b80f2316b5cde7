"""Ozaki-scheme GEMM: exactness of slicing + accuracy vs the DD oracle."""

from fractions import Fraction

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dd, ozaki
from repro.kernels.ref import ddgemm_ref


def _rand_dd(shape, rng, scale_lo=1e-20):
    hi = rng.standard_normal(shape)
    x = dd.from_float(jnp.asarray(hi))
    lo = rng.standard_normal(shape) * scale_lo
    return dd.add(x, dd.from_float(jnp.asarray(lo)))


def _max_rel_err(got: dd.DD, want: dd.DD):
    diff = np.abs(
        (np.asarray(got.hi, np.float64) - np.asarray(want.hi, np.float64))
        + (np.asarray(got.lo, np.float64) - np.asarray(want.lo, np.float64))
    )
    scale = np.maximum(np.abs(np.asarray(want.hi, np.float64)), 1e-30)
    return float((diff / scale).max())


def test_slice_extraction_is_error_free():
    rng = np.random.default_rng(0)
    a = _rand_dd((8, 16), rng)
    beta = 10
    slices = ozaki._extract_slices(a, beta, 12, axis=1)
    # slices must sum back to a (within the dropped remainder < 2^(-beta*12))
    total = dd.zeros(a.shape, jnp.float64)
    for s in slices:
        total = dd.add(total, dd.from_float(s))
    assert _max_rel_err(total, a) < 2.0 ** (-beta * 11)
    # each slice entry has <= beta+1 significant bits (grid-aligned)
    for s in slices:
        s_np = np.asarray(s)
        nz = s_np[s_np != 0]
        for v in nz[:50]:
            m, e = np.frexp(v)
            # value / its own grid must be a small integer
            assert float(m) * 2 ** (beta + 1) == int(float(m) * 2 ** (beta + 1))


@pytest.mark.parametrize("m,k,n", [(8, 8, 8), (16, 64, 12), (33, 128, 17)])
def test_ozaki_f64_matches_oracle(m, k, n):
    rng = np.random.default_rng(m * 1000 + k + n)
    a = _rand_dd((m, k), rng)
    b = _rand_dd((k, n), rng)
    got = ozaki.ozaki_gemm(a, b)
    want = ddgemm_ref(a, b)
    assert _max_rel_err(got, want) < 2.0**-95


def test_ozaki_badly_scaled_rows():
    # per-row grids must handle rows of wildly different magnitude
    rng = np.random.default_rng(7)
    a_np = rng.standard_normal((8, 32)) * (10.0 ** rng.integers(-18, 18, size=(8, 1)))
    b_np = rng.standard_normal((32, 8)) * (10.0 ** rng.integers(-18, 18, size=(1, 8)))
    a, b = dd.from_float(jnp.asarray(a_np)), dd.from_float(jnp.asarray(b_np))
    got = ozaki.ozaki_gemm(a, b)
    want = ddgemm_ref(a, b)
    assert _max_rel_err(got, want) < 2.0**-90


def test_ozaki_bf16_slices_small_k():
    # the MXU path: bf16 slices, f32 accumulation; k small enough for beta=8
    rng = np.random.default_rng(5)
    a = _rand_dd((16, 32), rng)
    b = _rand_dd((32, 16), rng)
    got = ozaki.ozaki_gemm(a, b, slice_dtype=jnp.bfloat16, acc_dtype=jnp.float32)
    want = ddgemm_ref(a, b)
    assert _max_rel_err(got, want) < 2.0**-90


def test_ozaki_full_vs_truncated():
    rng = np.random.default_rng(9)
    a = _rand_dd((8, 16), rng)
    b = _rand_dd((16, 8), rng)
    got_tri = ozaki.ozaki_gemm(a, b, full=False)
    got_full = ozaki.ozaki_gemm(a, b, full=True)
    want = ddgemm_ref(a, b)
    assert _max_rel_err(got_full, want) <= 2.0**-100
    assert _max_rel_err(got_tri, want) < 2.0**-95


def test_ozaki_exact_on_f64_inputs_small():
    # pure f64 inputs (lo = 0), tiny k: against exact Fraction products
    rng = np.random.default_rng(2)
    a_np = rng.standard_normal((4, 4))
    b_np = rng.standard_normal((4, 4))
    got = ozaki.ozaki_gemm(dd.from_float(jnp.asarray(a_np)), dd.from_float(jnp.asarray(b_np)), full=True)
    for i in range(4):
        for j in range(4):
            want = sum((Fraction(a_np[i, p]) * Fraction(b_np[p, j]) for p in range(4)), Fraction(0))
            gotf = Fraction(float(got.hi[i, j])) + Fraction(float(got.lo[i, j]))
            assert abs(float(gotf - want)) <= 2.0**-100 * max(1.0, abs(float(want)))


def test_slice_bits_and_count():
    assert ozaki.slice_bits(4096, jnp.float32, jnp.bfloat16) == 6
    assert ozaki.slice_bits(64, jnp.float32, jnp.bfloat16) == 8
    assert ozaki.slice_bits(256, jnp.float64) == 22
    assert ozaki.slice_count(107, 6) == 19


@pytest.mark.parametrize("backend,slice_dtype,acc_dtype", [
    ("ozaki", None, None),                    # f64 slices on the CPU
    ("ozaki-pallas", None, None),
    ("ozaki-pallas", "bfloat16", "float32"),  # the TPU's MXU dtypes
])
def test_f32_limb_product_keeps_df32_accuracy(backend, slice_dtype,
                                              acc_dtype):
    """dd over f32 limbs (the TPU's tier) keeps ~49 bits: a diagonal sum
    wider than the limbs is folded in its own dtype, not rounded to f32."""
    from repro import gemm
    from repro.core.accuracy import tier_ulp

    rng = np.random.default_rng(11)
    k = 48

    def operand(shape):
        x = rng.standard_normal(shape)
        hi = x.astype(np.float32)
        lo = (x - hi).astype(np.float32)
        exact = hi.astype(np.float64) + lo  # the value the limbs hold
        return dd.DD(jnp.asarray(hi), jnp.asarray(lo)), exact

    a, a64 = operand((16, k))
    b, b64 = operand((k, 12))
    got = gemm.matmul(a, b, backend=backend, slice_dtype=slice_dtype,
                      acc_dtype=acc_dtype, use_cache=False)
    want = ddgemm_ref(dd.from_float(jnp.asarray(a64)),
                      dd.from_float(jnp.asarray(b64)))
    got64 = dd.from_float(jnp.asarray(np.asarray(got.hi, np.float64)
                                      + np.asarray(got.lo, np.float64)))
    err = np.abs(np.asarray(dd.to_float(dd.sub(got64, want))))
    scale = max(1.0, float(np.abs(np.asarray(want.hi)).max()))
    assert err.max() / scale < 16 * k * tier_ulp("dd", jnp.float32)
