"""Property suite for the pivot / TRSM layer under the refinement solver.

Hypothesis-driven invariants (plus deterministic spot checks that run even
without hypothesis installed):

  * ``apply_pivots`` round-trip — forward interchanges followed by the
    inverse application is the identity, for any LAPACK-style pivot vector
    (piv[j] >= j) and any offset;
  * ``rtrsm`` left/right x unit/non-unit consistency — the returned X
    reproduces B through the *mp oracle* (a tier-arithmetic reference
    product), and the right-side solve agrees with the transpose identity;
  * ``rgetrf2`` (unblocked) and ``rgetrf(block=nb)`` agree — same pivots,
    same packed L\\U to tier accuracy — across random panel widths.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mp
from repro.core.accuracy import max_rel_err
from repro.core.linalg import (
    apply_pivots,
    pivot_permutation,
    rgetrf,
    rgetrf2,
    rtrsm,
)
from repro.kernels.ref import ddgemm_ref, qdgemm_ref

pytestmark = pytest.mark.solver

REF = {"dd": ddgemm_ref, "qd": qdgemm_ref}
ULP = {"dd": 2.0 ** -104, "qd": 2.0 ** -205}


def _rand(precision, shape, seed):
    rng = np.random.default_rng(seed)
    return mp.from_float(jnp.asarray(rng.standard_normal(shape)), precision)


def _rand_piv(rng, m):
    """LAPACK-style interchange vector: piv[j] in [j, m)."""
    return np.array([rng.integers(j, m) for j in range(m)], np.int32)


def _tri(rng, n, *, lower, unit_diag):
    t = rng.standard_normal((n, n))
    t = np.tril(t) if lower else np.triu(t)
    np.fill_diagonal(t, 1.0 if unit_diag else 3.0 + rng.random(n))
    return t


# -- deterministic spot checks (always run) --------------------------------


@pytest.mark.parametrize("precision", ["dd", "qd"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_apply_pivots_roundtrip(precision, seed):
    rng = np.random.default_rng(seed)
    m = int(rng.integers(2, 12))
    x = _rand(precision, (m, 3), seed)
    piv = jnp.asarray(_rand_piv(rng, m))
    back = apply_pivots(apply_pivots(x, piv), piv, inverse=True)
    assert max_rel_err(back, x) == 0.0  # pure gathers: bit-exact


@pytest.mark.parametrize("offset", [0, 2])
def test_pivot_permutation_matches_legacy_loop(offset):
    rng = np.random.default_rng(3)
    m, nb = 9, 5
    piv = _rand_piv(rng, nb)  # local panel pivots
    perm = np.arange(m)
    for j, p in enumerate(piv):  # the pre-traceable reference construction
        jj, pj = j + offset, int(p) + offset
        perm[jj], perm[pj] = perm[pj], perm[jj]
    got = np.asarray(pivot_permutation(jnp.asarray(piv), m, offset))
    assert (got == perm).all()


@pytest.mark.parametrize("precision", ["dd", "qd"])
@pytest.mark.parametrize("side", ["left", "right"])
@pytest.mark.parametrize("lower", [True, False])
@pytest.mark.parametrize("unit_diag", [True, False])
def test_rtrsm_consistency_vs_mp_oracle(precision, side, lower, unit_diag):
    rng = np.random.default_rng(11)
    n, k = 7, 4
    t_np = _tri(rng, n, lower=lower, unit_diag=unit_diag)
    t = mp.from_float(jnp.asarray(t_np), precision)
    bshape = (n, k) if side == "left" else (k, n)
    b = _rand(precision, bshape, 13)
    x = rtrsm(t, b, side=side, lower=lower, unit_diag=unit_diag)
    # mp oracle: op(T) X (or X op(T)) must reproduce B in tier arithmetic
    recon = REF[precision](t, x) if side == "left" else REF[precision](x, t)
    assert max_rel_err(recon, b) < 64 * n * ULP[precision]


def test_rtrsm_right_agrees_with_transpose_identity():
    rng = np.random.default_rng(17)
    n, k = 6, 3
    t = mp.from_float(jnp.asarray(_tri(rng, n, lower=True,
                                       unit_diag=False)), "dd")
    b = _rand("dd", (k, n), 19)
    via_right = rtrsm(t, b, side="right", lower=True)
    bt = mp.map_limbs(lambda l: l.T, b)
    via_left = rtrsm(t, bt, lower=True, transpose_a=True)
    assert max_rel_err(via_right, mp.map_limbs(lambda l: l.T, via_left)) == 0.0


def test_rtrsm_rejects_unknown_side():
    t = _rand("dd", (4, 4), 23)
    with pytest.raises(ValueError, match="side"):
        rtrsm(t, t, side="middle")


# At qd each case with more than one panel compiles its own panels, for
# about a minute under load: those are slow.  The dd column runs every
# width, and test_linalg_gates keeps the qd blocked LU in tier 1.
@pytest.mark.parametrize("n,nb,precision", [
    pytest.param(n, nb, p, marks=[pytest.mark.slow, pytest.mark.timeout(300)]
                 if p == "qd" and nb < n else [])
    for p in ("dd", "qd") for n, nb in [(8, 3), (12, 5), (9, 9), (10, 4)]])
def test_rgetrf_blocked_matches_unblocked(precision, n, nb):
    a = _rand(precision, (n, n), n * 7 + nb)
    full, piv_full = rgetrf2(a)
    blocked, piv_blk = rgetrf(a, block=nb)
    assert (np.asarray(piv_full) == np.asarray(piv_blk)).all()
    assert max_rel_err(blocked, full) < 64 * n * ULP[precision]


# -- hypothesis properties (skipped when hypothesis is unavailable; the
# deterministic spot checks above run regardless, so the layer is never
# entirely unexercised) ----------------------------------------------------

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on minimal installs
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    @st.composite
    def _piv_cases(draw):
        m = draw(st.integers(min_value=1, max_value=16))
        piv = [draw(st.integers(min_value=j, max_value=m - 1))
               for j in range(m)]
        return m, np.array(piv, np.int32)

    @given(_piv_cases(), st.sampled_from(["dd", "qd"]))
    @settings(max_examples=25, deadline=None)
    def test_prop_apply_pivots_roundtrip(case, precision):
        m, piv = case
        x = _rand(precision, (m, 2), m)
        back = apply_pivots(apply_pivots(x, jnp.asarray(piv)),
                            jnp.asarray(piv), inverse=True)
        assert max_rel_err(back, x) == 0.0

    # Shapes come from small fixed sets, so that examples reuse compiled
    # programs, and the draws are derandomized, so that every run takes the
    # same examples and the same time; the data stays random.
    @given(st.sampled_from([3, 7]),
           st.booleans(), st.booleans(), st.sampled_from(["left", "right"]),
           st.integers(min_value=0, max_value=2 ** 31 - 1))
    @settings(max_examples=15, deadline=None, derandomize=True)
    def test_prop_rtrsm_reconstructs_b(n, lower, unit_diag, side, seed):
        rng = np.random.default_rng(seed)
        t = mp.from_float(jnp.asarray(_tri(rng, n, lower=lower,
                                           unit_diag=unit_diag)), "dd")
        bshape = (n, 3) if side == "left" else (3, n)
        b = _rand("dd", bshape, seed % 1000)
        x = rtrsm(t, b, side=side, lower=lower, unit_diag=unit_diag)
        recon = REF["dd"](t, x) if side == "left" else REF["dd"](x, t)
        assert max_rel_err(recon, b) < 64 * n * ULP["dd"]

    @given(st.sampled_from([5, 8]),
           st.sampled_from([3, 8]),  # a ragged last panel, and one panel
           st.integers(min_value=0, max_value=2 ** 31 - 1))
    @settings(max_examples=15, deadline=None, derandomize=True)
    def test_prop_rgetrf_block_invariance(n, nb, seed):
        a = _rand("dd", (n, n), seed % 10_000)
        full, piv_full = rgetrf2(a)
        blocked, piv_blk = rgetrf(a, block=min(nb, n))
        assert (np.asarray(piv_full) == np.asarray(piv_blk)).all()
        assert max_rel_err(blocked, full) < 64 * n * ULP["dd"]
else:
    @pytest.mark.skip(reason="property tests need hypothesis "
                      "(pip install -r requirements-dev.txt)")
    def test_prop_suite_requires_hypothesis():
        pass
