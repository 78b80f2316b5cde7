"""The fsum references agree with exact rational arithmetic."""

from fractions import Fraction

import numpy as np
import pytest

import exact


def _limbs(rng, shape, nlimbs):
    """f32 limbs with signal in each, 2^-24 apart."""
    return [(rng.standard_normal(shape) * 2.0 ** (-24 * i)).astype(np.float32)
            for i in range(nlimbs)]


def _frac(limbs, idx):
    return sum((Fraction(float(l[idx])) for l in limbs), Fraction(0))


@pytest.mark.parametrize("nlimbs", [2, 3])
def test_product_errors_match_fractions(nlimbs):
    rng = np.random.default_rng(7)
    m = k = n = 24
    a, b = _limbs(rng, (m, k), nlimbs), _limbs(rng, (k, n), nlimbs)
    c = _limbs(rng, (m, n), nlimbs)
    rows, cols = exact.stratified_entries(rng, m, n, 4)
    got = exact.product_errors([l[rows] for l in a], [l[:, cols] for l in b],
                               [l[rows, cols] for l in c], block=5)
    for t, (i, j) in enumerate(zip(rows, cols)):
        dot = sum((_frac(a, (i, q)) * _frac(b, (q, j)) for q in range(k)),
                  Fraction(0))
        scale = sum(abs(sum(float(l[i, q]) for l in a)
                        * sum(float(l[q, j]) for l in b)) for q in range(k))
        want = abs(_frac(c, (i, j)) - dot) / Fraction(scale)
        assert got[t] == pytest.approx(float(want), rel=1e-12)


def test_product_error_of_an_exact_answer_is_zero():
    rng = np.random.default_rng(3)
    a = rng.integers(-8, 9, (8, 16)).astype(np.float32)
    b = rng.integers(-8, 9, (16, 8)).astype(np.float32)
    c = (a @ b).astype(np.float32)  # small integers: exact
    rows, cols = exact.stratified_entries(rng, 8, 8, 8)
    errs = exact.product_errors([a[rows]], [b[:, cols]], [c[rows, cols]])
    assert not errs.any()
    c[rows[3], cols[3]] += 1
    errs = exact.product_errors([a[rows]], [b[:, cols]], [c[rows, cols]])
    assert np.flatnonzero(errs).tolist() == [3]


def test_solve_errors_match_fractions():
    rng = np.random.default_rng(11)
    n = 16
    a, x, b = _limbs(rng, (n, n), 2), _limbs(rng, (n,), 2), _limbs(rng, (n,), 2)
    rows = np.array([0, 5, 15])
    norm = 3.5
    got = exact.solve_errors([l[rows] for l in a], x, [l[rows] for l in b],
                             norm)
    for t, i in enumerate(rows):
        r = _frac(b, i) - sum((_frac(a, (i, j)) * _frac(x, j)
                               for j in range(n)), Fraction(0))
        assert got[t] == pytest.approx(float(abs(r) / Fraction(norm)),
                                       rel=1e-12)


def test_stratified_entries_cover_every_band():
    rng = np.random.default_rng(0)
    rows, cols = exact.stratified_entries(rng, 64, 32, 8)
    assert len(rows) == 64
    assert set(rows // 8) == set(range(8)) and set(cols // 4) == set(range(8))


def test_refuses_f64_limbs():
    with pytest.raises(TypeError):
        exact.product_errors([np.zeros((1, 2))], [np.zeros((2, 1))],
                             [np.zeros(1)])
