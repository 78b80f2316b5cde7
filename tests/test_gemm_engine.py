"""Unified GEMM engine: plan/autotune/dispatch, batched + sharded paths.

Covers the ISSUE-1 acceptance surface: all four backends route through
GemmPlan/execute; batched results match a looped ref oracle to DD
tolerance; sharded row-partitioned execution matches the oracle (including
on a real multi-device mesh, via a subprocess with forced host devices);
tuned block shapes round-trip through the on-disk cache and are reused by
the planner.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import gemm
from repro.core import dd
from repro.core.blas import rgemm
from repro.kernels.ref import ddgemm_ref

DD_TOL = 2.0 ** -104


@pytest.fixture()
def tmp_cache(tmp_path):
    cache = gemm.PlanCache(str(tmp_path / "plans.json"))
    gemm.set_default_cache(cache)
    yield cache
    gemm.set_default_cache(None)


def _rand_dd(shape, seed):
    rng = np.random.default_rng(seed)
    return dd.from_float(jnp.asarray(rng.standard_normal(shape)))


def _dd_err(got: dd.DD, want: dd.DD) -> float:
    return float(np.abs(
        (np.asarray(got.hi, np.float64) - np.asarray(want.hi, np.float64))
        + (np.asarray(got.lo, np.float64) - np.asarray(want.lo, np.float64))
    ).max())


# --------------------------------------------------------------------------
# planning
# --------------------------------------------------------------------------


class TestPlan:
    def test_all_backends_route_through_plan(self, tmp_cache):
        a, b = _rand_dd((20, 12), 0), _rand_dd((12, 24), 1)
        want = ddgemm_ref(a, b)
        for be in ("pallas", "ozaki", "xla", "ref"):
            plan = gemm.make_plan(20, 12, 24, backend=be)
            assert plan.backend == be
            got = gemm.execute(plan, a, b)
            assert _dd_err(got, want) < 16 * 16 * DD_TOL * 10

    def test_backend_env_resolution(self, monkeypatch):
        monkeypatch.setenv("REPRO_GEMM_BACKEND", "xla")
        assert gemm.make_plan(8, 8, 8).backend == "xla"
        monkeypatch.delenv("REPRO_GEMM_BACKEND")
        assert gemm.make_plan(8, 8, 8).backend == "ozaki"
        with pytest.raises(ValueError):
            gemm.make_plan(8, 8, 8, backend="systolic9000")

    def test_blocks_clamped_to_problem(self, tmp_cache):
        plan = gemm.make_plan(10, 6, 20, backend="pallas")
        assert (plan.bm, plan.bn, plan.bk) == (16, 24, 8)

    def test_plan_and_overrides_are_exclusive(self, tmp_cache):
        plan = gemm.make_plan(8, 8, 8, backend="ref")
        a, b = _rand_dd((8, 8), 40), _rand_dd((8, 8), 41)
        with pytest.raises(ValueError, match="not both"):
            gemm.matmul(a, b, plan=plan, backend="ozaki")

    def test_unbatched_plan_rejects_batched_operands(self, tmp_cache):
        plan = gemm.make_plan(8, 8, 8, backend="ref")
        a, b = _rand_dd((3, 8, 8), 42), _rand_dd((8, 8), 43)
        with pytest.raises(ValueError, match="batch"):
            gemm.execute(plan, a, b)

    def test_plan_is_reusable_and_frozen(self, tmp_cache):
        plan = gemm.make_plan(16, 16, 16, backend="xla")
        a, b = _rand_dd((16, 16), 2), _rand_dd((16, 16), 3)
        c1, c2 = gemm.execute(plan, a, b), gemm.execute(plan, a, b)
        np.testing.assert_array_equal(np.asarray(c1.hi), np.asarray(c2.hi))
        with pytest.raises(Exception):
            plan.backend = "ref"


# --------------------------------------------------------------------------
# batched GEMM vs looped ref oracle
# --------------------------------------------------------------------------


class TestBatched:
    @pytest.mark.parametrize("backend", ["pallas", "ozaki", "xla", "ref"])
    def test_batched_a_matches_looped_oracle(self, backend, tmp_cache):
        a, b = _rand_dd((5, 14, 10), 4), _rand_dd((10, 12), 5)
        got = gemm.matmul(a, b, backend=backend)
        assert got.shape == (5, 14, 12)
        for i in range(5):
            want = ddgemm_ref(a[i], b)
            scale = max(1.0, float(np.abs(np.asarray(want.hi)).max()))
            assert _dd_err(got[i], want) < 16 * 14 * DD_TOL * scale

    def test_batched_both_and_broadcast(self, tmp_cache):
        a = _rand_dd((2, 3, 9, 7), 6)
        b = _rand_dd((3, 7, 11), 7)  # broadcasts over the leading 2
        got = gemm.matmul(a, b, backend="xla")
        assert got.shape == (2, 3, 9, 11)
        for i in range(2):
            for j in range(3):
                want = ddgemm_ref(a[i, j], b[j])
                assert _dd_err(got[i, j], want) < 16 * 7 * DD_TOL * 4

    def test_batched_b_only(self, tmp_cache):
        a = _rand_dd((6, 8), 8)
        b = _rand_dd((4, 8, 6), 9)
        got = gemm.matmul(a, b, backend="ozaki")
        for i in range(4):
            want = ddgemm_ref(a, b[i])
            assert _dd_err(got[i], want) < 16 * 8 * DD_TOL * 4


# --------------------------------------------------------------------------
# sharded GEMM
# --------------------------------------------------------------------------


_SHARD_SCRIPT = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec
from repro import gemm
from repro.core import dd
from repro.kernels.ref import ddgemm_ref

assert len(jax.devices()) == 2, jax.devices()
mesh = Mesh(np.array(jax.devices()), ("x",))
rng = np.random.default_rng(0)
a = dd.from_float(jnp.asarray(rng.standard_normal((30, 16))))
b = dd.from_float(jnp.asarray(rng.standard_normal((16, 12))))
want = ddgemm_ref(a, b)
for be in ("pallas", "ozaki-pallas", "xla"):
    got = gemm.matmul(a, b, backend=be, mesh=mesh)
    err = np.abs((np.asarray(got.hi) - np.asarray(want.hi))
                 + (np.asarray(got.lo) - np.asarray(want.lo))).max()
    assert err < 1e-28, (be, err)
# even-multiple M keeps the all-gather-free row-sharded output layout
a32 = dd.from_float(jnp.asarray(rng.standard_normal((32, 16))))
got = gemm.matmul(a32, b, backend="xla", mesh=mesh)
assert got.hi.sharding.spec == PartitionSpec("x"), got.hi.sharding
print("SHARDED_OK")
"""


_SUMMA_SCRIPT = r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec
from repro import gemm
from repro.core import mp
from repro.kernels.ref import ddgemm_ref, qdgemm_ref

assert len(jax.devices()) == 4, jax.devices()
mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("rows", "cols"))
ULP = {"dd": 2.0 ** -104, "qd": 2.0 ** -205}

def rnd(prec, s, seed):
    r = np.random.default_rng(seed)
    return mp.from_float(jnp.asarray(r.standard_normal(s)), prec)

def err(g, w):
    return float(max(np.abs(np.asarray(gl, np.float64)
                            - np.asarray(wl, np.float64)).max()
                     for gl, wl in zip(mp.limbs(g), mp.limbs(w))))

a, b = rnd("dd", (30, 40), 1), rnd("dd", (40, 12), 2)
want = ddgemm_ref(a, b)
gate = 16 * 40 * ULP["dd"] * 8
for be in ("xla", "ozaki-pallas"):
    assert err(gemm.matmul(a, b, backend=be, mesh=mesh), want) < gate, be
# qd tier on the same 2-D mesh
aq, bq = rnd("qd", (16, 24), 3), rnd("qd", (24, 8), 4)
assert err(gemm.matmul(aq, bq, backend="xla", mesh=mesh),
           qdgemm_ref(aq, bq)) < 16 * 24 * ULP["qd"] * 8
# even-multiple shapes keep the all-gather-free 2-D block-sharded layout
a32, b12 = rnd("dd", (32, 40), 5), rnd("dd", (40, 12), 6)
got = gemm.matmul(a32, b12, backend="xla", mesh=mesh)
assert got.hi.sharding.spec == PartitionSpec("rows", "cols"), \
    got.hi.sharding
# acceptance cell: batched + 2-D-sharded dd + full epilogue, ONE call
ab, c = rnd("dd", (3, 30, 40), 7), rnd("dd", (30, 12), 8)
got = gemm.matmul(ab, b, backend="xla", mesh=mesh,
                  alpha=2.0, beta=-0.5, c=c)
two = mp.from_float(jnp.asarray(2.0))
mhalf = mp.from_float(jnp.asarray(-0.5))
for i in range(3):
    w = ddgemm_ref(ab[i], b)
    w = mp.add(mp.mul(mp.broadcast_to(two, w.shape), w),
               mp.mul(mp.broadcast_to(mhalf, c.shape), c))
    assert err(got[i], w) < gate, i
# degenerate topologies through the same loop
for shape in ((1, 4), (4, 1)):
    m2 = Mesh(np.array(jax.devices()).reshape(shape), ("rows", "cols"))
    assert err(gemm.matmul(a, b, backend="xla", mesh=m2), want) < gate, shape
# production LM mesh names resolve through the gemm rule table
m3 = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
p3 = gemm.make_plan(30, 40, 12, backend="xla", mesh=m3)
assert (p3.shard_axis, p3.shard_axis_n) == ("data", "model")
assert err(gemm.execute(p3, a, b), want) < gate
print("SUMMA_OK")
"""


class TestSharded:
    def test_sharded_single_device_mesh(self, tmp_cache):
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:1]), ("rows",))
        a, b = _rand_dd((26, 10), 10), _rand_dd((10, 18), 11)
        want = ddgemm_ref(a, b)
        got = gemm.matmul(a, b, backend="xla", mesh=mesh)
        assert _dd_err(got, want) < 16 * 10 * DD_TOL * 4
        plan = gemm.make_plan(26, 10, 18, backend="xla", mesh=mesh)
        assert plan.shard_axis == "rows"

    def test_batched_plus_sharded_in_one_call(self, tmp_cache):
        # the old NotImplementedError path: vmap now composes outside the
        # SUMMA shard_map, so batched + sharded is ONE engine call
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
        plan = gemm.make_plan(8, 8, 8, backend="xla", mesh=mesh,
                              batch_shape=(2,))
        a, b = _rand_dd((2, 8, 8), 12), _rand_dd((8, 8), 13)
        got = gemm.execute(plan, a, b)
        assert got.shape == (2, 8, 8)
        for i in range(2):
            assert _dd_err(got[i], ddgemm_ref(a[i], b)) < 16 * 8 * DD_TOL * 4

    def test_column_only_sharding_runs_sharded(self, tmp_cache):
        # an explicit shard_axis_n= claiming a 1-axis mesh is pure column
        # sharding (shard_axis stays None) — it must run the SUMMA loop,
        # not silently fall through to the unsharded path
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
        plan = gemm.make_plan(16, 10, 24, backend="xla", mesh=mesh,
                              shard_axis_n="x")
        assert (plan.shard_axis, plan.shard_axis_n) == (None, "x")
        a, b = _rand_dd((16, 10), 16), _rand_dd((10, 24), 17)
        got = gemm.execute(plan, a, b)
        assert _dd_err(got, ddgemm_ref(a, b)) < 16 * 10 * DD_TOL * 4
        # (the column-sharded output layout is asserted on a real
        # multi-device mesh in _SUMMA_SCRIPT — a size-1 axis normalizes
        # to the replicated spec, so it is unobservable here)

    def test_summa_2d_mesh_single_device(self, tmp_cache):
        # a 2-axis (1, 1) mesh drives the full SUMMA loop (both mesh axes,
        # K-panel streaming) on one device — the always-on conformance cell
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                    ("rows", "cols"))
        plan = gemm.make_plan(26, 40, 18, backend="xla", mesh=mesh,
                              k_panel=8)
        assert (plan.shard_axis, plan.shard_axis_n) == ("rows", "cols")
        a, b = _rand_dd((26, 40), 14), _rand_dd((40, 18), 15)
        got = gemm.execute(plan, a, b)
        assert _dd_err(got, ddgemm_ref(a, b)) < 16 * 40 * DD_TOL * 4

    def test_sharded_two_forced_host_devices(self):
        out = _run_forced_devices(_SHARD_SCRIPT, 2)
        assert "SHARDED_OK" in out

    @pytest.mark.sharding
    def test_summa_four_forced_host_devices(self):
        # the ISSUE-5 acceptance cell: batched + 2-D-sharded dd GEMM in ONE
        # engine.execute call on a real 2x2 host-device mesh, vs the mp
        # oracle at the tier accuracy gates
        out = _run_forced_devices(_SUMMA_SCRIPT, 4)
        assert "SUMMA_OK" in out


def _run_forced_devices(script: str, ndev: int) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={ndev}")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    out = subprocess.run([sys.executable, "-c", script],
                         env=env, capture_output=True, text=True,
                         timeout=80)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


# --------------------------------------------------------------------------
# rgemm epilogue through the engine (nonsquare + transposed + DD scalars)
# --------------------------------------------------------------------------


class TestRgemmEpilogue:
    def test_nonsquare_transposed_dd_alpha_beta(self, tmp_cache):
        rng = np.random.default_rng(21)
        a_np = rng.standard_normal((9, 17))   # op(A) = A^T: (17, 9)
        b_np = rng.standard_normal((9, 13))   # op(B) = B:   (9, 13)
        c_np = rng.standard_normal((17, 13))
        third = dd.div(dd.from_float(jnp.asarray(1.0)),
                       dd.from_float(jnp.asarray(3.0)))     # 1/3, not f64
        seventh = dd.div(dd.from_float(jnp.asarray(-1.0)),
                         dd.from_float(jnp.asarray(7.0)))   # -1/7
        a, b = dd.from_float(jnp.asarray(a_np)), dd.from_float(jnp.asarray(b_np))
        c = dd.from_float(jnp.asarray(c_np))
        got = rgemm("t", "n", third, a, b, seventh, c, backend="xla")
        # DD oracle with the same DD epilogue
        prod = ddgemm_ref(dd.DD(a.hi.T, a.lo.T), b)
        want = dd.add(
            dd.mul(dd.DD(jnp.broadcast_to(third.hi, prod.shape),
                         jnp.broadcast_to(third.lo, prod.shape)), prod),
            dd.mul(dd.DD(jnp.broadcast_to(seventh.hi, c.shape),
                         jnp.broadcast_to(seventh.lo, c.shape)), c))
        assert _dd_err(got, want) < 1e-28
        # f64 sanity
        want_f64 = a_np.T @ b_np / 3.0 - c_np / 7.0
        assert np.abs(np.asarray(dd.to_float(got)) - want_f64).max() < 1e-13

    def test_batched_transpose_flag(self, tmp_cache):
        # 't' on a batched operand must swap only the matrix axes
        a = _rand_dd((4, 8, 6), 24)   # op(A): batch of (6, 8)
        b = _rand_dd((8, 5), 25)
        got = rgemm("t", "n", 1.0, a, b, 0.0, backend="xla")
        assert got.shape == (4, 6, 5)
        for i in range(4):
            want = ddgemm_ref(dd.DD(a.hi[i].T, a.lo[i].T), b)
            assert _dd_err(got[i], want) < 16 * 8 * DD_TOL * 4

    def test_rgemm_with_prebuilt_plan(self, tmp_cache):
        a, b = _rand_dd((12, 20), 22), _rand_dd((20, 8), 23)
        plan = gemm.make_plan(12, 20, 8, backend="pallas", bm=8, bn=8, bk=8)
        got = rgemm("n", "n", 1.0, a, b, 0.0, plan=plan)
        assert _dd_err(got, ddgemm_ref(a, b)) < 16 * 20 * DD_TOL * 4


# --------------------------------------------------------------------------
# autotune + plan cache round-trip
# --------------------------------------------------------------------------


class TestAutotuneCache:
    def test_cache_round_trip_on_disk(self, tmp_cache):
        key = gemm.cache_key("cpu", "float64", 100, 100, 100, "pallas")
        tmp_cache.put(key, {"bm": 32, "bn": 64, "bk": 8})
        # fresh object, same path -> reads from disk, not memory
        reread = gemm.PlanCache(tmp_cache.path)
        assert reread.get(key) == {"bm": 32, "bn": 64, "bk": 8}
        with open(tmp_cache.path) as f:
            assert key in json.load(f)

    def test_planner_uses_tuned_blocks_in_bucket(self, tmp_cache):
        key = gemm.cache_key("cpu", "float64", 100, 100, 100, "pallas")
        tmp_cache.put(key, {"bm": 32, "bn": 64, "bk": 8})
        # 100 and 120 share the 128-bucket -> both pick the tuned entry
        for mkn in (100, 120):
            plan = gemm.make_plan(mkn, mkn, mkn, backend="pallas",
                                  platform="cpu")
            assert plan.source == "tuned"
            assert (plan.bm, plan.bn, plan.bk) == (32, 64, 8)
        # explicit override beats the cache
        plan = gemm.make_plan(100, 100, 100, backend="pallas",
                              platform="cpu", bm=16)
        assert plan.source == "override" and plan.bm == 16
        # different bucket -> heuristic
        plan = gemm.make_plan(16, 16, 16, backend="pallas", platform="cpu")
        assert plan.source == "heuristic"

    def test_malformed_cache_entry_degrades_to_heuristic(self, tmp_cache):
        key = gemm.cache_key("cpu", "float64", 100, 100, 100, "pallas")
        tmp_cache.put(key, {"bm": 0, "bn": "lots", "bk": 8})
        plan = gemm.make_plan(100, 100, 100, backend="pallas",
                              platform="cpu")
        assert plan.source == "heuristic" and plan.bm > 0

    @pytest.mark.parametrize("garbage", [
        b'{"cpu/float64/128x128x128/pallas": {"bm": 32',  # truncated write
        b"\x00\x80 not json at all \xff",                 # binary noise
        b"[1, 2, 3]",                                     # valid JSON, wrong shape
    ])
    def test_corrupt_cache_file_warns_and_retunes(self, tmp_path, garbage):
        # a torn/garbled on-disk cache must cost a warning and a heuristic
        # plan, never an exception in every GEMM that consults the bucket
        path = tmp_path / "corrupt.json"
        path.write_bytes(garbage)
        cache = gemm.PlanCache(str(path))
        gemm.set_default_cache(cache)
        try:
            with pytest.warns(RuntimeWarning, match="cache"):
                plan = gemm.make_plan(100, 100, 100, backend="pallas",
                                      platform="cpu")
            assert plan.source == "heuristic" and plan.bm > 0
            # the poisoned file is recoverable: a put() rewrites it cleanly
            key = gemm.cache_key("cpu", "float64", 100, 100, 100, "pallas")
            cache.put(key, {"bm": 32, "bn": 64, "bk": 8})
            assert gemm.PlanCache(str(path)).get(key) == \
                {"bm": 32, "bn": 64, "bk": 8}
            replan = gemm.make_plan(100, 100, 100, backend="pallas",
                                    platform="cpu")
            assert replan.source == "tuned" and replan.bm == 32
        finally:
            gemm.set_default_cache(None)

    def test_autotune_persists_winner(self, tmp_cache, monkeypatch):
        # tuned under backend="auto": the entry must land under the RESOLVED
        # backend key, where make_plan will actually look it up
        monkeypatch.setenv("REPRO_GEMM_BACKEND", "pallas")
        cands = [{"bm": 16, "bn": 16, "bk": 8}, {"bm": 32, "bn": 32, "bk": 16}]
        plan = gemm.autotune(32, 32, 32, backend="auto",
                             candidates=cands, iters=1)
        assert plan.source == "tuned"
        assert {"bm": plan.bm, "bn": plan.bn, "bk": plan.bk} in cands
        replanned = gemm.make_plan(32, 32, 32, backend="pallas")
        assert replanned.source == "tuned"
        assert (replanned.bm, replanned.bn, replanned.bk) == \
            (plan.bm, plan.bn, plan.bk)

    def test_candidate_blocks_respect_vmem(self):
        for blk in gemm.candidate_blocks(4096, 4096, 4096):
            assert gemm.vmem_bytes(**blk) < 16 * 2**20

    def test_shape_bucket(self):
        assert gemm.shape_bucket(100, 100, 100) == "128x128x128"
        assert gemm.shape_bucket(128, 16, 1) == "128x16x8"

    def test_batched_plans_tune_apart_from_2d_bucket(self, tmp_cache):
        # since schema v3 the batch factor folds into the key — a
        # vmap-batched plan must NOT adopt tiles tuned for the 2-D bucket
        # (its VMEM pressure differs by the batch factor)
        k2d = gemm.cache_key("cpu", "float64", 100, 100, 100, "pallas")
        assert k2d.startswith(f"v{gemm.cache.SCHEMA}/")
        tmp_cache.put(k2d, {"bm": 32, "bn": 64, "bk": 8})
        plan = gemm.make_plan(100, 100, 100, backend="pallas",
                              platform="cpu", batch_shape=(5,))
        assert plan.source == "heuristic"  # 2-D entry not reused
        kb = gemm.cache_key("cpu", "float64", 100, 100, 100, "pallas",
                            batch_shape=(5,))
        assert kb != k2d
        tmp_cache.put(kb, {"bm": 16, "bn": 32, "bk": 8})
        plan = gemm.make_plan(100, 100, 100, backend="pallas",
                              platform="cpu", batch_shape=(5,))
        assert plan.source == "tuned"
        assert (plan.bm, plan.bn, plan.bk) == (16, 32, 8)
        # batch shapes bucket by flattened power-of-two size
        assert gemm.cache_key("cpu", "float64", 100, 100, 100, "pallas",
                              batch_shape=(2, 3)) == \
            gemm.cache_key("cpu", "float64", 100, 100, 100, "pallas",
                           batch_shape=(8,))

    def test_schema_v4_orphans_v3_rows_and_stale_quarantine(self, tmp_path):
        # schema v4 spells the limb count in the dtype segment for every
        # tier (``float64x2``, not bare ``float64`` for dd).  A cache file
        # written under v3 must degrade to heuristics (warn-free orphaning
        # — the rows are simply never consulted), re-tune into v4 keys,
        # and its stale/malformed quarantine rows must answer None rather
        # than crash plan-time quarantine checks.
        path = tmp_path / "plans.json"
        v3_rows = {
            # the old dd spelling (no limb-count suffix) and an old qd row
            "v3/cpu/float64/b1/128x128x128/pallas": {"bm": 64, "bn": 64,
                                                     "bk": 16},
            "v3/cpu/float64x4/b1/128x128x128/pallas": {"bm": 8, "bn": 8,
                                                       "bk": 8},
            # quarantine rows survive schema bumps (namespaced apart) but
            # malformed timestamps must read as expired, not raise
            "quarantine/v1/cpu/ozaki-pallas/x2": {"reason": "old",
                                                  "unix_time": "not-a-time"},
            "quarantine/v1/cpu/pallas/x3": {"reason": "no ts"},
        }
        path.write_text(json.dumps(v3_rows))
        cache = gemm.PlanCache(str(path))
        gemm.set_default_cache(cache)
        try:
            # v3 tuned rows are orphaned: both tiers fall back to heuristics
            for prec, v3_bm in (("dd", 64), ("qd", 8)):
                plan = gemm.make_plan(100, 100, 100, backend="pallas",
                                      platform="cpu", precision=prec)
                assert plan.source == "heuristic"
                assert plan.bm != v3_bm or plan.source == "heuristic"
            # stale quarantine rows: malformed timestamps answer None
            assert gemm.quarantined("cpu", "ozaki-pallas", 2) is None
            assert gemm.quarantined("cpu", "pallas", 3) is None
            # re-tuning writes v4 keys alongside the orphaned v3 rows
            for prec, nl in (("dd", 2), ("td", 3), ("qd", 4)):
                key = gemm.cache_key("cpu", "float64", 100, 100, 100,
                                     "pallas", nlimbs=nl)
                assert key.startswith("v4/") and f"float64x{nl}" in key
                cache.put(key, {"bm": 16, "bn": 32, "bk": 8})
                plan = gemm.make_plan(100, 100, 100, backend="pallas",
                                      platform="cpu", precision=prec)
                assert plan.source == "tuned"
                assert (plan.bm, plan.bn, plan.bk) == (16, 32, 8)
            # the orphaned rows are untouched on disk (no destructive
            # migration), and the v4 rows coexist with them
            on_disk = json.loads(path.read_text())
            assert all(k in on_disk for k in v3_rows)
        finally:
            gemm.set_default_cache(None)

    def test_autotune_populates_batched_bucket(self, tmp_cache):
        # autotune(batch_shape=) is the API that fills batched buckets:
        # the winner persists under the batched key, the 2-D bucket stays
        # untouched
        cands = [{"bm": 16, "bn": 16, "bk": 8}, {"bm": 8, "bn": 8, "bk": 8}]
        plan = gemm.autotune(16, 16, 16, backend="xla", batch_shape=(4,),
                             candidates=cands, iters=1)
        assert plan.batch == "vmap"
        replan = gemm.make_plan(16, 16, 16, backend="xla",
                                batch_shape=(4,))
        assert replan.source == "tuned"
        assert (replan.bm, replan.bn, replan.bk) == \
            (plan.bm, plan.bn, plan.bk)
        assert gemm.make_plan(16, 16, 16, backend="xla").source == \
            "heuristic"

    def test_explicit_cache_beats_env_var(self, tmp_cache, tmp_path,
                                          monkeypatch):
        # a cache installed via set_default_cache must win over
        # $REPRO_GEMM_CACHE pointing elsewhere
        monkeypatch.setenv("REPRO_GEMM_CACHE", str(tmp_path / "other.json"))
        assert gemm.default_cache() is tmp_cache


class TestCompatShim:
    def test_backend_kwargs_forwarded(self):
        # the legacy core.gemm.matmul surface still threads backend-specific
        # kwargs (ozaki slicing knobs, xla chunk) through the planner
        from repro.core.gemm import matmul as shim_matmul

        a, b = _rand_dd((10, 8), 30), _rand_dd((8, 12), 31)
        want = ddgemm_ref(a, b)
        got = shim_matmul(a, b, backend="ozaki", full=True, target_bits=107)
        assert _dd_err(got, want) < 16 * 8 * DD_TOL * 4
        got = shim_matmul(a, b, backend="xla", chunk=4)
        assert _dd_err(got, want) < 16 * 8 * DD_TOL * 4
