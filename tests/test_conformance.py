"""Cross-backend conformance matrix for the precision-generic GEMM engine.

One parametrized sweep over (backend x precision x shape x epilogue)
against the per-tier ``ref`` oracle (kernels/ref.py), with per-tier ulp
bounds.  Shapes include non-square and odd-K cases, so padding/clamping
in the engine is exercised at every limb count (dd/td/qd); the alpha/beta
cells run the full Rgemm epilogue with non-representable tier scalars
(1/3, -1/7).

The SUMMA axis runs the same product conformance over mesh topologies
(1x1, 1xN, Nx1, 2x2 — the 2-D SUMMA distribution layer) against the
qd-direct oracle at both tiers, plus the epilogue/batched cells; cells
needing more devices than the process has skip, and CI's ``sharding`` job
forces 4 host devices so every cell runs.

The solver axis extends the same discipline to ``repro.solve``: every
(factor_tier x target_tier) rung combination, on the plain, batched and
row-sharded multi-RHS paths, is conformance-checked against a qd-direct
oracle (full qd ``rgetrf`` + ``lu_solve`` — the most accurate solve the
repo can produce), plus refinement-convergence invariants (monotone
non-increasing backward error; escalation exactly on stagnation).

This is the test CI's ``conformance`` job runs on CPU interpret mode —
every cell of the support matrix must agree with its oracle before a
backend/tier combination is considered live.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import gemm
from repro.core import mp
from repro.core.accuracy import max_rel_err as _rel_err
from repro.core.accuracy import tier_ulp
from repro.core.blas import rgemm
from repro.core.linalg import lu_solve, rgetrf
from repro.kernels.ref import ddgemm_ref, qdgemm_ref, tdgemm_ref
from repro.solve import rgesv

# per-tier unit roundoff of one engine FMA (dd: two_prod slack dominates;
# td/qd: the O(eps^k) renormalization truncation)
ULP = {p: tier_ulp(p) for p in ("dd", "td", "qd")}
REF = {"dd": ddgemm_ref, "td": tdgemm_ref, "qd": qdgemm_ref}

# the support matrix: whole-K ozaki has no qd tier (rejected below,
# separately); every other backend serves every tier, and ozaki serves
# dd and td
CELLS = [(be, "dd") for be in ("pallas", "ozaki", "ozaki-pallas",
                               "xla", "ref")] + \
        [(be, "td") for be in ("pallas", "ozaki", "ozaki-pallas",
                               "xla", "ref")] + \
        [(be, "qd") for be in ("pallas", "ozaki-pallas", "xla", "ref")]

# square / non-square / odd-K (prime) so every backend pads and clamps
SHAPES = [(16, 16, 16), (13, 7, 9), (8, 33, 12)]


@pytest.fixture()
def tmp_cache(tmp_path):
    cache = gemm.PlanCache(str(tmp_path / "plans.json"))
    gemm.set_default_cache(cache)
    yield cache
    gemm.set_default_cache(None)


def _rand(precision, shape, seed):
    """Random multi-limb operand with signal in every limb."""
    rng = np.random.default_rng(seed)
    out = mp.from_float(jnp.asarray(rng.standard_normal(shape)), precision)
    for scale in (2.0 ** -53, 2.0 ** -106, 2.0 ** -159)[: mp.nlimbs(out) - 1]:
        extra = mp.from_float(
            jnp.asarray(rng.standard_normal(shape) * scale), precision)
        out = mp.add(out, extra)
    return out


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("backend,precision", CELLS)
def test_product_matches_tier_oracle(backend, precision, m, k, n, tmp_cache):
    a = _rand(precision, (m, k), seed=m * 31 + k)
    b = _rand(precision, (k, n), seed=n * 17 + k)
    want = REF[precision](a, b)
    got = gemm.matmul(a, b, backend=backend)
    assert mp.precision_of(got) == precision
    assert _rel_err(got, want) < 16 * k * ULP[precision]


# ozaki-pallas fuses alpha/beta into its kernel drain, which XLA:CPU
# compiles slowly in interpret mode: about a minute at td, and at qd it had
# not finished after ten minutes (its dd sibling compiles in seconds;
# tests/test_tpu_compile.py compiles the td drain for the TPU)
FUSED_DRAIN_MARKS = {
    "td": [pytest.mark.slow, pytest.mark.timeout(300)],
    "qd": [pytest.mark.slow, pytest.mark.timeout(3600)],
}
EPILOGUE_CELLS = [
    pytest.param(be, p, marks=FUSED_DRAIN_MARKS[p])
    if be == "ozaki-pallas" and p in FUSED_DRAIN_MARKS else (be, p)
    for be, p in CELLS]


@pytest.mark.parametrize("backend,precision", EPILOGUE_CELLS)
def test_alpha_beta_epilogue_in_tier(backend, precision, tmp_cache):
    m, k, n = 9, 11, 6  # odd everything: padding + epilogue broadcast
    a = _rand(precision, (m, k), seed=1)
    b = _rand(precision, (k, n), seed=2)
    c = _rand(precision, (m, n), seed=3)
    one = mp.from_float(jnp.asarray(1.0), precision)
    third = mp.div(one, mp.from_float(jnp.asarray(3.0), precision))
    m_seventh = mp.div(mp.neg(one), mp.from_float(jnp.asarray(7.0), precision))
    got = rgemm("n", "n", third, a, b, m_seventh, c, backend=backend)
    prod = REF[precision](a, b)
    want = mp.add(mp.mul(mp.broadcast_to(third, prod.shape), prod),
                  mp.mul(mp.broadcast_to(m_seventh, c.shape), c))
    assert _rel_err(got, want) < 16 * k * ULP[precision]


@pytest.mark.parametrize("backend,precision", CELLS)
def test_batched_matches_looped_oracle(backend, precision, tmp_cache):
    a = _rand(precision, (3, 7, 5), seed=4)
    b = _rand(precision, (5, 8), seed=5)
    got = gemm.matmul(a, b, backend=backend)
    assert got.shape == (3, 7, 8)
    for i in range(3):
        want = REF[precision](a[i], b)
        assert _rel_err(got[i], want) < 16 * 5 * ULP[precision]


def test_transpose_flags_compose_with_tiers(tmp_cache):
    for precision in ("dd", "td", "qd"):
        a = _rand(precision, (7, 10), seed=6)   # op(A) = A^T: (10, 7)
        b = _rand(precision, (7, 4), seed=7)
        got = rgemm("t", "n", 1.0, a, b, 0.0, backend="xla")
        want = REF[precision](
            mp.map_limbs(lambda l: l.T, a), b)
        assert _rel_err(got, want) < 16 * 7 * ULP[precision]


def test_ozaki_has_no_qd_tier(tmp_cache):
    a = _rand("qd", (8, 8), seed=8)
    with pytest.raises(ValueError, match="ozaki"):
        gemm.matmul(a, a, backend="ozaki")


def test_mixed_tier_operands_rejected(tmp_cache):
    a = _rand("dd", (8, 8), seed=9)
    b = _rand("qd", (8, 8), seed=10)
    with pytest.raises(TypeError, match="tier"):
        gemm.matmul(a, b, backend="xla")


def test_plan_precision_must_match_operands(tmp_cache):
    plan = gemm.make_plan(8, 8, 8, backend="xla", precision="qd")
    a = _rand("dd", (8, 8), seed=11)
    with pytest.raises(ValueError, match="precision"):
        gemm.execute(plan, a, a)


# --------------------------------------------------------------------------
# solver axis: (factor_tier x target_tier) x (plain | batched | sharded)
# conformance-checked against the qd-direct oracle
# --------------------------------------------------------------------------

from repro.solve import LADDER_CELLS as SOLVER_CELLS  # noqa: E402

_SOLVER_N, _SOLVER_NRHS = 12, 2


@pytest.fixture(scope="module")
def solver_oracle():
    """qd-direct solve (full qd rgetrf + lu_solve): the accuracy ceiling.

    One panel (block = n): the oracle's answer is the same, and it compiles
    in half the time of two panels."""
    rng = np.random.default_rng(31)
    a = rng.standard_normal((_SOLVER_N, _SOLVER_N)) + _SOLVER_N * np.eye(
        _SOLVER_N)
    b = rng.standard_normal((_SOLVER_N, _SOLVER_NRHS))
    a_qd = mp.from_float(jnp.asarray(a), "qd")
    b_qd = mp.from_float(jnp.asarray(b), "qd")
    lu, piv = rgetrf(a_qd, block=_SOLVER_N)
    return a, b, lu_solve(lu, piv, b_qd)


@pytest.mark.solver
@pytest.mark.parametrize("mode", ["plain", "batched", "sharded"])
@pytest.mark.parametrize("factor_tier,target_tier", SOLVER_CELLS)
def test_solver_matches_qd_direct_oracle(factor_tier, target_tier, mode,
                                         solver_oracle, tmp_cache):
    a, b, x_oracle = solver_oracle
    kwargs = dict(factor_tier=factor_tier, target_tier=target_tier,
                  backend="xla")
    if mode == "sharded":
        from jax.sharding import Mesh

        kwargs["mesh"] = Mesh(np.array(jax.devices()[:1]), ("rows",))
    if mode == "batched":
        # 2x is a power of two: the scaled RHS (and hence its solution)
        # is exact at every tier, so the oracle scales exactly too
        got, info = rgesv(a, np.stack([b, 2.0 * b]), **kwargs)
        assert got.shape == (2, _SOLVER_N, _SOLVER_NRHS)
        cells = [(got[0], x_oracle),
                 (got[1], mp.mul_float(x_oracle, jnp.float64(2.0)))]
    else:
        got, info = rgesv(a, b, **kwargs)
        cells = [(got, x_oracle)]
    assert info.converged, (factor_tier, target_tier, mode,
                            info.backward_errors)
    # refinement must deliver the *target tier's* accuracy no matter how
    # cheap the factorization rung was
    for x, want in cells:
        err = _rel_err(mp.promote(x, "qd"), want)
        assert err < 64 * _SOLVER_N * ULP[target_tier], \
            (factor_tier, target_tier, mode, err)


@pytest.mark.solver
@pytest.mark.parametrize("factor_tier,target_tier", SOLVER_CELLS)
def test_refinement_backward_error_monotone(factor_tier, target_tier,
                                            solver_oracle, tmp_cache):
    a, b, _ = solver_oracle
    _, info = rgesv(a, b, factor_tier=factor_tier, target_tier=target_tier,
                    backend="xla")
    h = info.backward_errors
    assert all(later <= earlier for earlier, later in zip(h, h[1:])), h
    assert not info.escalations  # well-conditioned: no rung ever stagnates


@pytest.mark.solver
def test_escalation_fires_exactly_on_stagnation(tmp_cache):
    from repro.core.accuracy import hilbert_f64

    n = 14  # cond ~ 1e18: f64 corrections crawl, the dd rung finishes
    h = hilbert_f64(n)
    b = h @ np.ones((n, 1))
    _, info = rgesv(h, b, factor_tier="f64", target_tier="dd",
                    backend="xla", max_iters=25)
    assert info.converged
    assert [(e["from"], e["to"]) for e in info.escalations] == \
        [("f64", "dd")]
    # the escalation iteration is exactly the first stagnating one
    berrs = info.backward_errors
    it = info.escalations[0]["iteration"]
    assert berrs[it - 1] > 0.25 * berrs[it - 2]
    assert all(berrs[i] <= 0.25 * berrs[i - 1] for i in range(2, it - 1))


# --------------------------------------------------------------------------
# SUMMA axis: mesh topologies vs the qd-direct oracle, dd and qd
# --------------------------------------------------------------------------

# (rows, cols) topologies; cells needing more devices than the process has
# skip (CI's `sharding` job forces 4 host devices so every cell runs)
_MESHES = [(1, 1), (1, 2), (2, 1), (2, 2)]


def _mesh(rows: int, cols: int):
    from jax.sharding import Mesh

    if jax.device_count() < rows * cols:
        pytest.skip(f"needs {rows * cols} devices, have {jax.device_count()}")
    return Mesh(np.array(jax.devices()[: rows * cols]).reshape(rows, cols),
                ("rows", "cols"))


@pytest.mark.sharding
@pytest.mark.parametrize("rows,cols", _MESHES)
@pytest.mark.parametrize("precision", ["dd", "qd"])
def test_summa_matches_qd_direct_oracle(rows, cols, precision, tmp_cache):
    mesh = _mesh(rows, cols)
    m, k, n = 13, 23, 9  # odd everything: every dim pads against the mesh
    a = _rand(precision, (m, k), seed=60)
    b = _rand(precision, (k, n), seed=61)
    # qd-direct product: the most accurate GEMM the repo can produce —
    # climbing to qd is exact, so this bounds the dd cells' true error too
    want = qdgemm_ref(mp.promote(a, "qd"), mp.promote(b, "qd"))
    got = gemm.matmul(a, b, backend="xla", mesh=mesh, k_panel=8)
    assert mp.precision_of(got) == precision
    assert _rel_err(mp.promote(got, "qd"), want) < 16 * k * ULP[precision]


@pytest.mark.sharding
@pytest.mark.parametrize("rows,cols", _MESHES)
def test_summa_epilogue_and_batch_match_oracle(rows, cols, tmp_cache):
    mesh = _mesh(rows, cols)
    m, k, n = 13, 23, 9
    a = _rand("dd", (2, m, k), seed=62)  # batched + sharded, one call
    b = _rand("dd", (k, n), seed=63)
    c = _rand("dd", (m, n), seed=64)
    one = mp.from_float(jnp.asarray(1.0), "dd")
    third = mp.div(one, mp.from_float(jnp.asarray(3.0), "dd"))
    m7th = mp.div(mp.neg(one), mp.from_float(jnp.asarray(7.0), "dd"))
    got = rgemm("n", "n", third, a, b, m7th, c, backend="xla", mesh=mesh)
    assert got.shape == (2, m, n)
    for i in range(2):
        prod = ddgemm_ref(a[i], b)
        want = mp.add(mp.mul(mp.broadcast_to(third, prod.shape), prod),
                      mp.mul(mp.broadcast_to(m7th, c.shape), c))
        assert _rel_err(got[i], want) < 16 * k * ULP["dd"]


@pytest.mark.solver
@pytest.mark.sharding
def test_solver_multi_rhs_on_2d_mesh(solver_oracle, tmp_cache):
    # refined solves ride the SUMMA layer: batched multi-RHS residuals on
    # a 2-axis mesh (rows x RHS columns) through one engine call per step
    a, b, x_oracle = solver_oracle
    rows = 2 if jax.device_count() >= 2 else 1
    cols = 2 if jax.device_count() >= 4 else 1
    mesh = _mesh(rows, cols)
    got, info = rgesv(a, np.stack([b, 2.0 * b]), factor_tier="f64",
                      target_tier="dd", backend="xla", mesh=mesh)
    assert info.converged, info.backward_errors
    cells = [(got[0], x_oracle),
             (got[1], mp.mul_float(x_oracle, jnp.float64(2.0)))]
    for x, want in cells:
        assert _rel_err(mp.promote(x, "qd"), want) < \
            64 * _SOLVER_N * ULP["dd"]


# --------------------------------------------------------------------------
# ring-vs-psum: the ppermute ring panel schedule must be BIT-IDENTICAL to
# the legacy masked-psum broadcast (same panels, same fold order — PR 9's
# conformance gate for the comm rewrite), plus the uneven-K and k_stream
# exactness regressions
# --------------------------------------------------------------------------

# multi-device topologies only: on 1x1 both schedules degenerate to the
# same no-comm loop
_RING_MESHES = [(1, 2), (2, 1), (2, 2)]


def _limbs_equal(x, y):
    for lx, ly in zip(mp.limbs(x), mp.limbs(y)):
        np.testing.assert_array_equal(np.asarray(lx), np.asarray(ly))


def _comm_pair(m, k, n, mesh, precision="dd", **kw):
    return tuple(
        gemm.make_plan(m, k, n, backend="xla", precision=precision,
                       mesh=mesh, comm=comm, use_cache=False, **kw)
        for comm in ("ring", "psum"))


@pytest.mark.sharding
@pytest.mark.parametrize("rows,cols", _RING_MESHES)
@pytest.mark.parametrize("precision", ["dd", "qd"])
def test_ring_bit_identical_to_psum(rows, cols, precision, tmp_cache):
    mesh = _mesh(rows, cols)
    m, k, n = 13, 23, 9
    a = _rand(precision, (m, k), seed=70)
    b = _rand(precision, (k, n), seed=71)
    ring, psum = _comm_pair(m, k, n, mesh, precision, k_panel=8)
    _limbs_equal(gemm.execute(ring, a, b), gemm.execute(psum, a, b))


@pytest.mark.sharding
@pytest.mark.parametrize("rows,cols", _RING_MESHES)
def test_ring_epilogue_and_batched_bit_identical(rows, cols, tmp_cache):
    mesh = _mesh(rows, cols)
    m, k, n = 13, 23, 9
    a = _rand("dd", (2, m, k), seed=72)  # batched + sharded + epilogue
    b = _rand("dd", (k, n), seed=73)
    c = _rand("dd", (m, n), seed=74)
    ring, psum = _comm_pair(m, k, n, mesh, "dd", k_panel=8,
                            batch_shape=(2,))
    _limbs_equal(
        gemm.execute(ring, a, b, alpha=0.5, beta=-2.0, c=c),
        gemm.execute(psum, a, b, alpha=0.5, beta=-2.0, c=c))


@pytest.mark.sharding
@pytest.mark.parametrize("k,k_panel", [
    (23, 8),   # K not divisible by kp * lcm(Pr, Pc)
    (3, 8),    # K smaller than one panel
    (7, 16),   # K smaller than a panel round on every topology
])
def test_ring_uneven_k_bit_identical(k, k_panel, tmp_cache):
    mesh = _mesh(2, 2)
    m, n = 13, 9
    a = _rand("dd", (m, k), seed=75)
    b = _rand("dd", (k, n), seed=76)
    ring, psum = _comm_pair(m, k, n, mesh, "dd", k_panel=k_panel)
    got = gemm.execute(ring, a, b)
    _limbs_equal(got, gemm.execute(psum, a, b))
    want = qdgemm_ref(mp.promote(a, "qd"), mp.promote(b, "qd"))
    assert _rel_err(mp.promote(got, "qd"), want) < 16 * max(k, 8) * ULP["dd"]


@pytest.mark.sharding
@pytest.mark.parametrize("k,k_stream", [
    (23, 5),   # chunk not dividing K (and not panel-aligned: rounds up)
    (23, 8),   # chunk == panel depth
    (40, 16),  # several whole chunks + ragged tail
])
def test_k_stream_bit_identical_to_unstreamed(k, k_stream, tmp_cache):
    mesh = _mesh(2, 2)
    m, n = 13, 9
    a = _rand("dd", (m, k), seed=77)
    b = _rand("dd", (k, n), seed=78)
    plan = gemm.make_plan(m, k, n, backend="xla", mesh=mesh, k_panel=8,
                          use_cache=False)
    whole = gemm.execute(plan, a, b)
    _limbs_equal(gemm.execute(plan, a, b, k_stream=k_stream), whole)
    # the plan-field spelling streams identically to the per-call override
    planned = gemm.make_plan(m, k, n, backend="xla", mesh=mesh, k_panel=8,
                             k_stream=k_stream, use_cache=False)
    _limbs_equal(gemm.execute(planned, a, b), whole)


@pytest.mark.sharding
def test_k_stream_requires_mesh(tmp_cache):
    plan = gemm.make_plan(8, 8, 8, backend="xla", use_cache=False)
    a = _rand("dd", (8, 8), seed=79)
    with pytest.raises(ValueError, match="k_stream"):
        gemm.execute(plan, a, a, k_stream=4)
    with pytest.raises(ValueError, match="mesh"):
        gemm.make_plan(8, 8, 8, backend="xla", k_stream=4, use_cache=False)


def test_qd_tiles_tune_independently(tmp_cache):
    # same bucket, different limb count -> different cache rows
    kd = gemm.cache_key("cpu", "float64", 100, 100, 100, "pallas", nlimbs=2)
    kq = gemm.cache_key("cpu", "float64", 100, 100, 100, "pallas", nlimbs=4)
    assert kd != kq
    tmp_cache.put(kd, {"bm": 32, "bn": 64, "bk": 8})
    tmp_cache.put(kq, {"bm": 16, "bn": 32, "bk": 8})
    pd = gemm.make_plan(100, 100, 100, backend="pallas", platform="cpu")
    pq = gemm.make_plan(100, 100, 100, backend="pallas", platform="cpu",
                        precision="qd")
    assert (pd.bm, pd.bn, pd.bk) == (32, 64, 8) and pd.source == "tuned"
    assert (pq.bm, pq.bn, pq.bk) == (16, 32, 8) and pq.source == "tuned"
    assert pq.nlimbs == 4
