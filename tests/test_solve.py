"""Unit tests for the tiered iterative-refinement solver (repro.solve).

Covers the refinement contract end-to-end: convergence across
(factor_tier x target_tier) rungs, escalation firing exactly on
stagnation, monotone backward-error histories, NaN-robust escalation when
a cheap rung's factorization breaks down outright, the batched and
sharded multi-RHS paths, factorization reuse, and the compile-once-
per-plan regression (jit-traceable pivots keep the whole refinement step
inside one compiled function).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mp
from repro.core.accuracy import hilbert_f64
from repro.core.linalg import rgetrf, rpotrf
from repro.gemm import matmul
from repro.solve import (
    LADDER_CELLS,
    cholesky_solve_refined,
    lu_solve_refined,
    rgesv,
    rposv,
    tier_eps,
)
from repro.solve import refine as refine_mod

pytestmark = pytest.mark.solver


def _system(n=16, nrhs=2, seed=0, scale=None):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)) + n * np.eye(n)
    x = rng.standard_normal((n, nrhs))
    return a, a @ x, x


@pytest.mark.parametrize("factor_tier,target_tier", LADDER_CELLS)
def test_converges_across_ladder(factor_tier, target_tier):
    a, b, x_true = _system()
    x, info = rgesv(a, b, factor_tier=factor_tier, target_tier=target_tier,
                    backend="xla")
    assert info.converged and not info.escalations
    assert info.final_backward_error <= info.tol
    assert mp.precision_of(x) == target_tier
    assert np.abs(np.asarray(mp.to_float(x)) - x_true).max() < 1e-12
    # factored exactly once, at the requested rung
    assert info.factorizations == {factor_tier: 1}


def test_escalation_triggers_exactly_on_stagnation():
    # Hilbert n=14: cond ~ 1e18 crawls at ratio ~0.3 per f64-corrected
    # step — past the stagnation threshold — then one dd correction lands
    # inside tolerance
    n = 14
    h = hilbert_f64(n)
    b = h @ np.ones((n, 1))
    x, info = rgesv(h, b, factor_tier="f64", target_tier="dd",
                    backend="xla", max_iters=25)
    assert info.converged
    assert len(info.escalations) == 1
    assert info.factorizations == {"f64": 1, "dd": 1}
    # the recorded escalations are exactly the iterations whose
    # backward-error ratio crossed the stagnation threshold
    berrs = info.backward_errors
    crossed = set()
    stale = 0.25  # the default stagnation_ratio
    for i in range(2, len(berrs) + 1):
        if berrs[i - 1] > stale * berrs[i - 2] and not crossed:
            crossed.add(i)  # first crossing escalates; ladder then capped
    assert {e["iteration"] for e in info.escalations} == crossed
    for e in info.escalations:
        assert e["ratio"] > stale
        assert (e["from"], e["to"]) == ("f64", "dd")
    # post-escalation iterations run on the escalated rung
    esc_it = info.escalations[0]["iteration"]
    assert all(t == "f64" for t in info.factor_tiers[:esc_it])
    assert all(t == "dd" for t in info.factor_tiers[esc_it:])


@pytest.mark.parametrize("factor_tier,target_tier", LADDER_CELLS)
def test_backward_error_history_monotone_non_increasing(factor_tier,
                                                        target_tier):
    seed = LADDER_CELLS.index((factor_tier, target_tier))
    a, b, _ = _system(seed=seed)
    _, info = rgesv(a, b, factor_tier=factor_tier, target_tier=target_tier,
                    backend="xla")
    h = info.backward_errors
    assert all(later <= earlier for earlier, later in zip(h, h[1:])), h


@pytest.mark.parametrize("ladder", ["default", "three-rung"])
def test_nan_factor_breakdown_escalates_and_recovers(ladder):
    # SPD with an eigenvalue (1e-40) far below dd resolution of the large
    # ones: the dd Cholesky goes indefinite under rounding and NaNs; the
    # solver must escalate one rung and still converge.  On the default
    # ladder the next rung is td (~159 bits, resolving cond ~1e40 with
    # room to spare), so the breakdown recovers WITHOUT a qd
    # factorization; the old three-rung ladder must still climb to qd.
    # One ladder a case: each compiles its own rungs.
    n = 6
    rng = np.random.default_rng(5)
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    qq = mp.from_float(jnp.asarray(q), "qd")
    d = mp.from_float(jnp.asarray(np.diag([1.0] * (n - 1) + [1e-40])), "qd")
    b_mat = matmul(matmul(qq, d, backend="xla"),
                   mp.map_limbs(lambda l: l.T, qq), backend="xla")
    rhs = mp.from_float(jnp.asarray(rng.standard_normal((n, 1))), "qd")
    kw = {} if ladder == "default" else {"ladder": ("f64", "dd", "qd")}
    x, info = rposv(b_mat, rhs, factor_tier="dd", target_tier="qd",
                    backend="xla", max_iters=20, tol=1e-30, **kw)
    assert info.converged, info.backward_errors
    if ladder == "default":
        assert len(info.escalations) == 1
        assert info.factorizations == {"dd": 1, "td": 1}
        assert np.isfinite(np.asarray(mp.to_float(x))).all()
    else:
        # the pre-td ladder spelling still climbs straight to qd
        assert "qd" in info.factorizations


@pytest.mark.parametrize("ladder", ["default", "three-rung"])
def test_td_rung_spares_the_qd_factorization(ladder):
    # The td rung's reason to exist: a system whose conditioning sits
    # between dd's reach (1/u_dd ~ 1e32) and td's (1/u_td ~ 7e47).
    # Hilbert n=26 (cond ~ 1e38) formed IN qd arithmetic — a multi-limb
    # division, so the conditioning is real, not flattened by f64
    # rounding — makes every dd-factored correction stagnate, while a td
    # factorization converges to the qd target.
    #
    # Receipt (the ISSUE acceptance criterion): on the default ladder the
    # solver climbs f64 -> dd -> td and never factors qd; on the old
    # three-rung ladder (f64, dd, qd) the same system must pay for a full
    # qd factorization.  One ladder a case: each compiles its own rungs.
    n = 26
    i = jnp.arange(n, dtype=jnp.float64)
    denom = i[:, None] + i[None, :] + 1.0
    h = mp.div(mp.from_float(jnp.ones((n, n)), "qd"),
               mp.from_float(denom, "qd"))
    b = matmul(h, mp.from_float(jnp.ones((n, 1)), "qd"), backend="xla")

    kw = {} if ladder == "default" else {"ladder": ("f64", "dd", "qd")}
    x, info = rgesv(h, b, target_tier="qd", backend="xla", max_iters=40,
                    **kw)
    assert info.converged, info.backward_errors
    if ladder == "default":
        assert "qd" not in info.factorizations, info.factorizations
        assert info.factorizations.get("td", 0) >= 1
        assert [(e["from"], e["to"]) for e in info.escalations] == \
            [("f64", "dd"), ("dd", "td")]
        assert info.factor_tiers[-1] == "td"
    else:
        assert info.factorizations.get("qd", 0) >= 1, info.factorizations
    # both ladders land the exact solution (all ones) at qd accuracy:
    # within 5e-26 each, so within 1e-25 of each other
    ones = mp.from_float(jnp.ones((n, 1)), "qd")
    assert np.abs(np.asarray(mp.to_float(mp.sub(x, ones)))).max() < 5e-26


def test_ladder_override_validation():
    a, b, _ = _system()
    # unknown rung
    with pytest.raises(ValueError, match="unknown tier"):
        rgesv(a, b, ladder=("f64", "xx"))
    # not strictly ascending
    with pytest.raises(ValueError, match="ascending"):
        rgesv(a, b, ladder=("dd", "f64"))
    with pytest.raises(ValueError, match="ascending"):
        rgesv(a, b, ladder=("dd", "dd"))
    # factor/target must be rungs of the ladder
    with pytest.raises(ValueError, match="ladder"):
        rgesv(a, b, factor_tier="td", ladder=("f64", "dd", "qd"))
    with pytest.raises(ValueError, match="ladder"):
        rgesv(a, b, target_tier="qd", ladder=("f64", "dd", "td"))
    # a valid custom ladder works and caps the climb at its top rung
    x, info = rgesv(a, b, target_tier="td", ladder=("dd", "td"))
    assert info.converged and mp.precision_of(x) == "td"
    assert info.factorizations == {"dd": 1}


def test_backward_error_is_per_column():
    # LAPACK xGERFS-style metric: a 1e12-scaled RHS column must not mask
    # a small-scale column still above its own backward-error target
    n = 10
    rng = np.random.default_rng(3)
    a = rng.standard_normal((n, n)) + n * np.eye(n)
    b = np.hstack([a @ rng.standard_normal((n, 1)),
                   1e12 * (a @ rng.standard_normal((n, 1)))])
    x, info = rgesv(a, b, factor_tier="f64", target_tier="dd",
                    backend="xla")
    assert info.converged
    from repro.kernels.ref import ddgemm_ref

    a_dd = mp.from_float(jnp.asarray(a), "dd")
    b_dd = mp.from_float(jnp.asarray(b), "dd")
    r = mp.sub(ddgemm_ref(a_dd, x), b_dd)
    rcol = np.max(np.abs(np.asarray(r.hi) + np.asarray(r.lo)), axis=0)
    xcol = np.max(np.abs(np.asarray(mp.to_float(x))), axis=0)
    anorm = np.abs(a).sum(axis=1).max()
    berr_cols = rcol / (anorm * xcol + np.abs(b).max(axis=0))
    assert berr_cols.max() <= info.tol, berr_cols


def test_batched_multi_rhs_matches_looped():
    rng = np.random.default_rng(7)
    n, nrhs, nb = 10, 2, 3
    a = rng.standard_normal((n, n)) + n * np.eye(n)
    b = rng.standard_normal((nb, n, nrhs))
    xb, info = rgesv(a, b, factor_tier="f64", target_tier="dd",
                     backend="xla")
    assert info.converged and xb.shape == (nb, n, nrhs)
    for i in range(nb):
        xi, _ = rgesv(a, b[i], factor_tier="f64", target_tier="dd",
                      backend="xla")
        d = np.abs(np.asarray(mp.to_float(xb[i]))
                   - np.asarray(mp.to_float(xi))).max()
        assert d < 1e-13


def test_sharded_multi_rhs_single_device_mesh():
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("rows",))
    a, b, x_true = _system(n=12, nrhs=3, seed=11)
    x, info = rgesv(a, b, factor_tier="f64", target_tier="dd",
                    backend="xla", mesh=mesh)
    assert info.converged
    assert np.abs(np.asarray(mp.to_float(x)) - x_true).max() < 1e-12


def test_lu_solve_refined_reuses_factorization():
    a, b, _ = _system(n=12, seed=13)
    a_dd = mp.from_float(jnp.asarray(a), "dd")
    lu, piv = rgetrf(a_dd, block=8)
    x, info = lu_solve_refined(a_dd, lu, piv, b, target_tier="qd",
                               backend="xla")
    assert info.converged
    assert info.factorizations == {}  # never re-factored
    assert info.final_backward_error <= info.tol


def test_cholesky_solve_refined_reuses_factorization():
    a, _, _ = _system(n=12, seed=17)
    s = a @ a.T + 12 * np.eye(12)
    rng = np.random.default_rng(17)
    b = s @ rng.standard_normal((12, 2))
    s_dd = mp.from_float(jnp.asarray(s), "dd")
    l = rpotrf(s_dd)
    x, info = cholesky_solve_refined(s_dd, l, b, target_tier="qd",
                                     backend="xla")
    assert info.converged and info.factorizations == {}


def test_target_tier_inferred_from_operand():
    a, b, _ = _system(n=8, seed=19)
    x, info = rgesv(mp.from_float(jnp.asarray(a), "qd"), b,
                    factor_tier="dd", backend="xla")
    assert info.target_tier == "qd" and mp.precision_of(x) == "qd"


def test_rejects_invalid_tiers_and_arg_combos():
    a, b, _ = _system(n=6, seed=23)
    with pytest.raises(ValueError, match="target_tier"):
        rgesv(a, b, factor_tier="f64", target_tier="f64")
    with pytest.raises(ValueError, match="ladder"):
        rgesv(a, b, factor_tier="qd", target_tier="dd")
    with pytest.raises(ValueError, match="assume"):
        rgesv(a, b, assume="sym")
    with pytest.raises(ValueError, match="unknown tier"):
        rgesv(a, b, factor_tier="fp8")
    plan = __import__("repro.gemm", fromlist=["make_plan"]).make_plan(
        6, 6, 2, precision="dd", backend="xla")
    with pytest.raises(ValueError, match="not both"):
        rgesv(a, b, target_tier="dd", plan=plan, backend="xla")


def test_replan_precision_resolves_tier_dependent_params():
    from repro.gemm import make_plan, replan_precision

    p = make_plan(16, 16, 4, precision="dd", backend="ozaki", platform="cpu")
    q = replan_precision(p, 16, 16, 4, "qd")
    assert q.precision == "qd" and q.backend == "xla"  # ozaki has no qd tier
    p2 = make_plan(16, 16, 4, precision="dd", backend="ozaki-pallas",
                   platform="cpu")
    q2 = replan_precision(p2, 16, 16, 4, "qd")
    # the slice fixpoint re-solves for the 212-bit coverage target
    assert q2.backend == "ozaki-pallas" and q2.target_bits == 212
    assert q2.n_slices > p2.n_slices
    assert replan_precision(p2, 16, 16, 4, "dd") is p2  # no-op same tier


def test_rgesv_replans_mismatched_plan_precision():
    from repro.gemm import make_plan

    a, b, _ = _system(n=8, seed=31)
    plan = make_plan(8, 8, 2, precision="dd", backend="xla")
    x, info = rgesv(mp.from_float(jnp.asarray(a), "qd"), b,
                    factor_tier="dd", plan=plan)
    assert info.target_tier == "qd" and info.converged
    assert mp.precision_of(x) == "qd"


def test_rgesv_compiles_once_per_plan():
    # the ISSUE-4 regression: pivots are traced JAX arrays end-to-end, so
    # the whole refinement step jit-compiles once per plan and repeat
    # solves with the same plan re-trace nothing
    n, nrhs = 17, 3  # unique shapes: nothing in this process traced them
    a, b, _ = _system(n=n, nrhs=nrhs, seed=29)
    log = refine_mod._TRACE_EVENTS
    before = len(log)
    rgesv(a, b, factor_tier="dd", target_tier="dd", backend="xla")
    first = log[before:]
    # one residual trace for the plan, one correction trace for the rung
    assert [e[0] for e in first] == ["residual", "correct"]
    mid = len(log)
    rgesv(a, b, factor_tier="dd", target_tier="dd", backend="xla")
    assert len(log) == mid, log[mid:]  # same plan: zero new traces
