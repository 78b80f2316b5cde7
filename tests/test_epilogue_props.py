"""Rgemm epilogue semantics property suite (ISSUE 5 satellites).

Three guarantees, each regression-tested here:

  * **beta needs C** — ``execute(plan, a, b, beta=0.5)`` with ``c=None``
    used to silently drop beta (``_apply_epilogue`` only read it under
    ``if c is not None``); it now raises ``ValueError``, mirroring the
    alpha/c defaulting rules.  ``beta=0`` without C stays legal — that is
    the BLAS "C is not read" spelling every Rgemm caller uses.
  * **beta == 0 means C is NOT read** — a NaN/Inf C must not leak through
    ``0 * C``.  Covered for statically-zero betas (python float and tier
    scalar: the engine drops the C term before any arithmetic), and for
    *traced* zeros on both epilogue implementations: the tier post-step
    (``_apply_epilogue``'s where-guard) and the fused ozaki-pallas kernel
    drain.
  * **one epilogue, every path** — plain 2-D, vmap-batched, 1-axis
    sharded, and 2-D SUMMA-sharded execution apply the identical tier
    arithmetic: all four agree with the ``mp`` oracle cell-for-cell.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import gemm
from repro.core import mp
from repro.core.accuracy import max_rel_err as _rel_err
from repro.core.blas import rgemm
from repro.kernels.ref import ddgemm_ref, qdgemm_ref

ULP = {"dd": 2.0 ** -104, "qd": 2.0 ** -205}
REF = {"dd": ddgemm_ref, "qd": qdgemm_ref}


@pytest.fixture()
def tmp_cache(tmp_path):
    cache = gemm.PlanCache(str(tmp_path / "plans.json"))
    gemm.set_default_cache(cache)
    yield cache
    gemm.set_default_cache(None)


def _rand(precision, shape, seed):
    rng = np.random.default_rng(seed)
    return mp.from_float(jnp.asarray(rng.standard_normal(shape)), precision)


def _poisoned(precision, shape, bad):
    """A C operand whose every entry is NaN or Inf (in the leading limb)."""
    hi = jnp.full(shape, jnp.nan if bad == "nan" else jnp.inf)
    limbs = [hi] + [jnp.zeros(shape)] * (mp.PRECISIONS[precision] - 1)
    return mp.from_limbs(limbs)


# --------------------------------------------------------------------------
# beta without C
# --------------------------------------------------------------------------


class TestBetaRequiresC:
    @pytest.mark.parametrize("beta", [0.5, -1.0])
    def test_nonzero_float_beta_without_c_raises(self, beta, tmp_cache):
        plan = gemm.make_plan(8, 8, 8, backend="xla")
        a, b = _rand("dd", (8, 8), 0), _rand("dd", (8, 8), 1)
        with pytest.raises(ValueError, match="beta"):
            gemm.execute(plan, a, b, beta=beta)

    def test_nonzero_tier_scalar_beta_without_c_raises(self, tmp_cache):
        plan = gemm.make_plan(8, 8, 8, backend="xla")
        a, b = _rand("dd", (8, 8), 0), _rand("dd", (8, 8), 1)
        with pytest.raises(ValueError, match="beta"):
            gemm.execute(plan, a, b,
                         beta=mp.from_float(jnp.asarray(0.25), "dd"))

    def test_rgemm_surface_raises_too(self, tmp_cache):
        a, b = _rand("dd", (8, 8), 0), _rand("dd", (8, 8), 1)
        with pytest.raises(ValueError, match="beta"):
            rgemm("n", "n", 1.0, a, b, 0.5, backend="xla")

    @pytest.mark.parametrize("beta", [0, 0.0])
    def test_beta_zero_without_c_is_the_blas_noop(self, beta, tmp_cache):
        # every BLAS caller writes rgemm(..., beta=0, C): "C is not read"
        plan = gemm.make_plan(8, 8, 8, backend="xla")
        a, b = _rand("dd", (8, 8), 0), _rand("dd", (8, 8), 1)
        got = gemm.execute(plan, a, b, beta=beta)
        assert _rel_err(got, ddgemm_ref(a, b)) < 16 * 8 * ULP["dd"]

    def test_tier_scalar_zero_beta_without_c_ok(self, tmp_cache):
        plan = gemm.make_plan(8, 8, 8, backend="xla")
        a, b = _rand("dd", (8, 8), 0), _rand("dd", (8, 8), 1)
        got = gemm.execute(plan, a, b, beta=mp.zeros((), "dd"))
        assert _rel_err(got, ddgemm_ref(a, b)) < 16 * 8 * ULP["dd"]


# --------------------------------------------------------------------------
# beta == 0 does not read C (NaN/Inf regression)
# --------------------------------------------------------------------------


class TestBetaZeroDoesNotReadC:
    @pytest.mark.parametrize("bad", ["nan", "inf"])
    @pytest.mark.parametrize("backend,precision", [
        ("xla", "dd"), ("xla", "qd"), ("ref", "dd"),
        ("pallas", "dd"), ("ozaki-pallas", "dd"),
        # the fused alpha drain at qd: XLA:CPU had not compiled it in
        # interpret mode after ten minutes
        pytest.param("ozaki-pallas", "qd",
                     marks=[pytest.mark.slow, pytest.mark.timeout(3600)]),
    ])
    def test_static_zero_beta_guards_poisoned_c(self, backend, precision,
                                                bad, tmp_cache):
        m, k, n = 9, 11, 6
        a = _rand(precision, (m, k), 2)
        b = _rand(precision, (k, n), 3)
        c = _poisoned(precision, (m, n), bad)
        got = rgemm("n", "n", 1.0, a, b, 0.0, c, backend=backend)
        assert np.isfinite(np.asarray(mp.limbs(got)[0])).all()
        assert _rel_err(got, REF[precision](a, b)) < 16 * k * ULP[precision]

    def test_static_tier_scalar_zero_beta_guards(self, tmp_cache):
        a, b = _rand("dd", (8, 8), 4), _rand("dd", (8, 8), 5)
        c = _poisoned("dd", (8, 8), "nan")
        got = rgemm("n", "n", 1.0, a, b, mp.zeros((), "dd"), c,
                    backend="xla")
        assert np.isfinite(np.asarray(got.hi)).all()

    @pytest.mark.parametrize("backend", ["xla", "ozaki-pallas"])
    def test_traced_zero_beta_guards_poisoned_c(self, backend, tmp_cache):
        # beta only known zero at RUN time (a tracer): the post-step
        # where-guard and the fused kernel drain must both mask 0 * NaN.
        # Compared against the mp oracle AND the un-jitted plain product:
        # the engine pins padded operands behind an optimization_barrier,
        # so an outer jit over constant operands is bit-identical to the
        # eager call (the pre-existing ~1e-17 interpret-mode drift this
        # test used to paper over is fixed)
        m, k, n = 9, 11, 6
        plan = gemm.make_plan(m, k, n, backend=backend)
        a, b = _rand("dd", (m, k), 6), _rand("dd", (k, n), 7)
        c = _poisoned("dd", (m, n), "nan")

        @jax.jit
        def run(beta):
            return gemm.execute(plan, a, b, alpha=1.0, beta=beta, c=c)

        got = run(mp.from_float(jnp.asarray(0.0), "dd"))
        plain = gemm.execute(plan, a, b)  # eager, un-jitted
        assert np.isfinite(np.asarray(got.hi)).all()
        assert _rel_err(got, plain) < 4 * ULP["dd"]
        assert _rel_err(got, ddgemm_ref(a, b)) < 16 * k * ULP["dd"]
        # the jitted plain product matches the eager one bit-for-bit (the
        # constant-folding divergence this suite used to work around)
        jplain = jax.jit(lambda: gemm.execute(plan, a, b))()
        for le, lj in zip(mp.limbs(plain), mp.limbs(jplain)):
            np.testing.assert_array_equal(np.asarray(le), np.asarray(lj))
        # ...and a traced NONZERO beta still reads C normally
        clean = _rand("dd", (m, n), 8)

        @jax.jit
        def run2(beta):
            return gemm.execute(plan, a, b, alpha=1.0, beta=beta, c=clean)

        bval = mp.from_float(jnp.asarray(-0.5), "dd")
        got = run2(bval)
        want = mp.add(plain,
                      mp.mul(mp.broadcast_to(bval, clean.shape), clean))
        assert _rel_err(got, want) < 16 * k * ULP["dd"]

    def test_batched_beta_zero_guard(self, tmp_cache):
        a = _rand("dd", (3, 8, 8), 9)
        b = _rand("dd", (8, 8), 10)
        c = _poisoned("dd", (8, 8), "nan")
        got = rgemm("n", "n", 1.0, a, b, 0.0, c, backend="xla")
        assert got.shape == (3, 8, 8)
        assert np.isfinite(np.asarray(got.hi)).all()


# --------------------------------------------------------------------------
# epilogue agreement: plain / batched / 1-axis sharded / 2-D SUMMA
# --------------------------------------------------------------------------


class TestEpiloguePathAgreement:
    @pytest.mark.parametrize("precision", ["dd", "qd"])
    @pytest.mark.parametrize("mode", ["plain", "batched", "sharded",
                                      "summa2d"])
    def test_modes_agree_with_mp_oracle(self, mode, precision, tmp_cache):
        from jax.sharding import Mesh

        m, k, n = 9, 21, 6  # odd everything: padding + K-panel remainder
        a = _rand(precision, (m, k), 11)
        b = _rand(precision, (k, n), 12)
        c = _rand(precision, (m, n), 13)
        one = mp.from_float(jnp.asarray(1.0), precision)
        third = mp.div(one, mp.from_float(jnp.asarray(3.0), precision))
        m7th = mp.div(mp.neg(one), mp.from_float(jnp.asarray(7.0),
                                                 precision))
        kwargs = dict(backend="xla")
        if mode == "sharded":
            kwargs["mesh"] = Mesh(np.array(jax.devices()[:1]), ("rows",))
        elif mode == "summa2d":
            kwargs["mesh"] = Mesh(
                np.array(jax.devices()[:1]).reshape(1, 1), ("rows", "cols"))
            kwargs["k_panel"] = 8  # forces a multi-step SUMMA loop
        if mode == "batched":
            a = mp.map_limbs(lambda l: jnp.stack([l, l * 2.0]), a)
        got = rgemm("n", "n", third, a, b, m7th, c, **kwargs)
        prod = REF[precision](a[0] if mode == "batched" else a, b)
        want = mp.add(mp.mul(mp.broadcast_to(third, prod.shape), prod),
                      mp.mul(mp.broadcast_to(m7th, c.shape), c))
        gate = 16 * k * ULP[precision]
        if mode == "batched":
            assert _rel_err(got[0], want) < gate
            # 2x scaling is exact: the second element's oracle scales too
            want2 = mp.add(
                mp.mul(mp.broadcast_to(third, prod.shape),
                       mp.mul_float(prod, jnp.float64(2.0))),
                mp.mul(mp.broadcast_to(m7th, c.shape), c))
            assert _rel_err(got[1], want2) < gate
        else:
            assert _rel_err(got, want) < gate
