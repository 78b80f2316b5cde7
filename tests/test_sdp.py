"""SDP / PDIPM tests — reproduces the paper's Table V claim structure:

double precision stalls near 1e-8..1e-12 relative gap; binary128-class
arithmetic pushes the same algorithm to ~1e-23 gaps with ~1e-32 dual
feasibility (measured on the Lovasz-theta family, the paper's own SDPLIB
problem class).
"""

import numpy as np
import pytest

from repro.core.sdp import random_sdp, solve_sdp, theta_problem

# module-scoped cache: the DD solve is expensive, reuse across assertions
_RESULTS = {}


def _theta_dd():
    if "dd" not in _RESULTS:
        _RESULTS["dd"] = solve_sdp(
            theta_problem(8, 0.4, seed=2), precision="binary128", max_iters=80)
    return _RESULTS["dd"]


def _theta_double():
    if "f64" not in _RESULTS:
        _RESULTS["f64"] = solve_sdp(
            theta_problem(8, 0.4, seed=2), precision="double", max_iters=40)
    return _RESULTS["f64"]


def test_binary128_reaches_table_v_band():
    res = _theta_dd()
    # Table V band: relative gaps 1e-22..1e-31, feasibility errors <= 1e-24
    assert res.relative_gap < 1e-20, res.relative_gap
    assert res.p_feas_err < 1e-20
    assert res.d_feas_err < 1e-28


def test_double_stalls_binary128_does_not():
    rd = _theta_double()
    rq = _theta_dd()
    # the paper's qualitative claim: >= 10 decades between precisions
    assert rq.relative_gap < 1e-10 * rd.relative_gap


def test_objective_agreement():
    # theta number of this graph is integral here (=4): both precisions agree
    rd = _theta_double()
    rq = _theta_dd()
    assert abs(rd.primal_obj - rq.primal_obj) < 1e-6
    assert abs(rq.primal_obj - rq.dual_obj) < 1e-18


def test_double_on_random_sdp_known_optimum():
    prob = random_sdp(8, 5, seed=3)
    res = solve_sdp(prob, precision="double", max_iters=40)
    assert res.relative_gap < 1e-6
    assert abs(res.primal_obj - prob.opt) < 1e-5 * max(1, abs(prob.opt))


@pytest.mark.slow
@pytest.mark.timeout(900)  # two PDIPM solves: about 6 minutes on a CPU
def test_qd_tier_descends_past_the_dd_floor():
    # ISSUE-2 acceptance: binary128+ reaches <= 1e-20 on random_sdp where
    # the dd tier floors higher.  degeneracy=1e-5 makes two constraints
    # nearly parallel (cond(B) ~ 1e10): the dd Schur-solve noise floors the
    # gap near 1e-24 (observed 1.3e-24, flat over the final iterations);
    # the qd tier's noise sits ~30 decades lower and the SAME algorithm
    # keeps descending and converges (observed 8.9e-28 at 63 iterations,
    # pfeas ~2e-63) — the paper's "binary128 or higher" clause, realized.
    prob = random_sdp(6, 4, seed=3, degeneracy=1e-5)
    rdd = solve_sdp(prob, precision="binary128", max_iters=80)
    rqd = solve_sdp(prob, precision="binary128+", max_iters=90,
                    tol_gap=1e-26)
    assert rqd.relative_gap <= 1e-20, rqd.relative_gap
    assert rqd.converged
    assert rdd.relative_gap > 1e-25, rdd.relative_gap   # dd floors higher
    assert rqd.relative_gap < 1e-2 * rdd.relative_gap
    # ISSUE-4 acceptance: the Schur solves reach the qd accuracy floor via
    # dd-factor + qd-refine (repro.solve rgesv) — measurably cheaper than
    # qd-direct: on this cond(B)~1e10 instance every solve's factorization
    # stays on the dd rung (observed: 118 solves, 0 qd factorizations,
    # gap 6.7e-27 — the qd-direct floor at dd factorization cost)
    st = rqd.schur_stats
    assert st is not None and st["solves"] > 0
    qd_factors = st["factorizations"].get("qd", 0)
    assert qd_factors < st["solves"] // 2, st
    assert st["factorizations"].get("dd", 0) > 0, st


@pytest.mark.slow
@pytest.mark.timeout(300)  # compiles the td PDIPM: 60-85 s on a CPU
def test_binary192_tier_solves_and_overrides_schur_factor():
    # the td rung of the SDP precision axis: binary192 runs the same PDIPM
    # in 3-limb arithmetic, converging where double stalls, and the Schur
    # path accepts an explicit factor-rung override (its solves then start
    # on that rung of the refinement ladder instead of dd)
    prob = random_sdp(6, 4, seed=3)
    res = solve_sdp(prob, precision="binary192", max_iters=50,
                    tol_gap=1e-18)
    assert res.converged and res.relative_gap <= 1e-18
    assert abs(res.primal_obj - prob.opt) < 1e-8 * max(1, abs(prob.opt))
    res_td = solve_sdp(prob, precision="binary192", max_iters=50,
                       tol_gap=1e-18, schur_factor_tier="td")
    assert res_td.converged
    assert res_td.schur_stats["factorizations"].get("td", 0) > 0
    with pytest.raises(ValueError, match="schur_factor_tier"):
        solve_sdp(prob, precision="double", schur_factor_tier="td")


def test_theta_problem_structure():
    prob = theta_problem(6, 0.5, seed=0)
    assert prob.a[0].shape == (6, 6)
    assert np.allclose(prob.a[0], np.eye(6))
    assert prob.b[0] == 1.0
    # constraint matrices are symmetric
    for a in prob.a:
        assert np.allclose(a, a.T)


def test_random_sdp_certificate():
    # generator must produce a genuinely optimal certificate pair
    prob = random_sdp(8, 4, seed=1)
    # b_i = A_i . X*, and opt = C . X* = b^T y* by construction
    assert prob.opt is not None
    assert np.isfinite(prob.opt)
