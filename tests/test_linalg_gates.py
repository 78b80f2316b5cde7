"""LAPACK-grade residual gates for the extended-precision linalg stack.

The classic LAPACK test ratios, at every ladder rung and with *exact*
measurement: factorization residuals are evaluated in rational arithmetic
(``core.accuracy``'s Fraction helpers) over the representable multi-limb
entries, so the gate pins the factorization's own backward error with
zero measurement noise:

    rgetrf:  ||P A - L U||  / (n ||A|| u_tier)  <= THRESH
    rpotrf:  ||A - L L^T||  / (n ||A|| u_tier)  <= THRESH
    rgesv :  ||A x - b|| / (||A|| ||x|| + ||b||) <= 4 n u_tier

THRESH = 30 is LAPACK's own acceptance constant.  Matrices cover the
well-conditioned case and the two canonical ill-conditioned families —
Hilbert (cond ~ e^{3.5n}) and graded-diagonal (rows spanning ~12 decades)
— because backward-stability gates must hold *independently of
conditioning*; that is precisely what they certify.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mp
from repro.core.accuracy import (
    frac_matmul,
    frac_matrix,
    frac_max_abs,
    frac_sub,
    hilbert_f64,
)
from repro.core.linalg import apply_pivots, rgetrf, rpotrf
from repro.solve import rgesv, rposv, tier_eps

pytestmark = pytest.mark.solver

THRESH = 30.0  # LAPACK's standard residual-ratio acceptance constant
TIERS = ("dd", "qd")
N = 10  # Fraction arithmetic is O(n^3) with ~tier-width operands


def _well_conditioned(n: int, seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, n)) + n * np.eye(n)


def _graded(n: int, seed: int = 1) -> np.ndarray:
    """Graded-diagonal matrix: row scales spanning ~12 decades."""
    rng = np.random.default_rng(seed)
    g = rng.standard_normal((n, n)) + n * np.eye(n)
    scales = np.logspace(0, -12, n)
    return scales[:, None] * g


MATRICES = {
    "rand": _well_conditioned(N),
    "hilbert": hilbert_f64(N),
    "graded": _graded(N),
}


def _spd(a: np.ndarray) -> np.ndarray:
    return a @ a.T + len(a) * np.eye(len(a))


SPD_MATRICES = {
    "rand": _spd(_well_conditioned(N)),
    "hilbert": hilbert_f64(N),  # already SPD
    "graded": _spd(_graded(N)) * np.outer(np.logspace(0, -6, N),
                                          np.logspace(0, -6, N)),
}


def _tri_parts(lu, n: int):
    """Split packed L\\U into unit-lower L and upper U, in tier arithmetic."""
    tril = jnp.asarray(np.tril(np.ones((n, n)), -1))
    triu = jnp.asarray(np.triu(np.ones((n, n))))
    eye = jnp.eye(n)
    l = mp.from_limbs([lim * tril + (eye if i == 0 else 0.0)
                       for i, lim in enumerate(mp.limbs(lu))])
    u = mp.map_limbs(lambda lim: lim * triu, lu)
    return l, u


@pytest.mark.parametrize("tier", TIERS)
@pytest.mark.parametrize("name", sorted(MATRICES))
def test_rgetrf_residual_gate(tier, name):
    a_np = MATRICES[name]
    a = mp.from_float(jnp.asarray(a_np), tier)
    # two panels: the blocked path, with a third fewer programs to compile
    # at qd than three panels of 4
    lu, piv = rgetrf(a, block=5)
    l, u = _tri_parts(lu, N)
    pa = apply_pivots(a, piv)
    resid = frac_sub(frac_matrix(pa), frac_matmul(frac_matrix(l),
                                                  frac_matrix(u)))
    anorm = frac_max_abs(frac_matrix(a))
    ratio = frac_max_abs(resid) / (N * anorm * tier_eps(tier))
    assert ratio <= THRESH, (name, tier, ratio)


@pytest.mark.parametrize("tier", TIERS)
@pytest.mark.parametrize("name", sorted(SPD_MATRICES))
def test_rpotrf_residual_gate(tier, name):
    a_np = SPD_MATRICES[name]
    a = mp.from_float(jnp.asarray(a_np), tier)
    l = rpotrf(a)
    fl = frac_matrix(l)
    flt = [list(row) for row in zip(*fl)]
    resid = frac_sub(frac_matrix(a), frac_matmul(fl, flt))
    anorm = frac_max_abs(frac_matrix(a))
    ratio = frac_max_abs(resid) / (N * anorm * tier_eps(tier))
    assert ratio <= THRESH, (name, tier, ratio)


@pytest.mark.parametrize("tier", TIERS)
@pytest.mark.parametrize("name", sorted(MATRICES))
def test_rgesv_backward_error_gate(tier, name):
    a_np = MATRICES[name]
    rng = np.random.default_rng(7)
    b_np = a_np @ rng.standard_normal((N, 2))
    x, info = rgesv(a_np, b_np, factor_tier="f64", target_tier=tier,
                    backend="xla", max_iters=30)
    assert info.converged, (name, tier, info.backward_errors)
    # independent exact-rational residual of the returned iterate
    a_t = mp.from_float(jnp.asarray(a_np), tier)
    b_t = mp.from_float(jnp.asarray(b_np), tier)
    resid = frac_sub(frac_matmul(frac_matrix(a_t), frac_matrix(x)),
                     frac_matrix(b_t))
    anorm = float(np.abs(a_np).max())
    xnorm = float(np.abs(np.asarray(mp.to_float(x))).max())
    bnorm = float(np.abs(b_np).max())
    berr = frac_max_abs(resid) / (anorm * xnorm + bnorm)
    assert berr <= 4 * N * tier_eps(tier), (name, tier, berr)


@pytest.mark.parametrize("tier", TIERS)
def test_rposv_backward_error_gate(tier):
    a_np = SPD_MATRICES["rand"]
    rng = np.random.default_rng(9)
    b_np = a_np @ rng.standard_normal((N, 2))
    x, info = rposv(a_np, b_np, factor_tier="f64", target_tier=tier,
                    backend="xla", max_iters=30)
    assert info.converged
    r = a_np @ np.asarray(mp.to_float(x)) - b_np  # f64 check only
    assert np.abs(r).max() < 1e-12  # exact gate covered by rgesv above
    assert info.final_backward_error <= 4 * N * tier_eps(tier)
