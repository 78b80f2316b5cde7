"""Compile rehearsals for a TPU v5e, without the chip.

The TPU compiler is installed beside JAX's CPU backend, so the kernels and
the engine's default TPU plans are compiled here for a *described* v5e
(``jax.experimental.topologies``): a kernel or plan that the chip's
compiler would refuse fails in tier 1, not on the chip.  Nothing runs, so
these tests say nothing about results or times (``chip_smoke.py`` does).

This is the only file that describes the topology.  It does so inside a
module fixture, never at import: one process at a time may load the TPU
library, and pytest-xdist workers each import every test file.

The support matrix on the TPU is pinned here too: every backend x tier x
limb-dtype cell either plans (and its default cells compile below) or is
refused by ``make_plan`` with the reason, so no cell reaches the engine's
failover walk.  Limb arithmetic that plans no GEMM (the LU's panels and
solves, Cholesky, plain dd ops) is refused over f64 limbs when lowered.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import gemm
from repro.core import mp
from repro.core.linalg import lu_solve, rgetrf, rpotrf
from repro.gemm import engine
from repro.gemm.plan import (TPU_F64_REFUSAL, TPU_REF_REFUSAL,
                             TPU_XLA_REFUSAL)

N = 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without
    # one: keep the persistent cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _limbs(shape, nl, sharding, dtype=jnp.float32):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)] * nl


# XLA's default level, which the chip's compiles get: the root conftest
# lowers XLA_FLAGS' level for the CPU, and that flag reaches these compiles
XLA_DEFAULT_OPT_LEVEL = 3


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile(compiler_options={
        "xla_backend_optimization_level": XLA_DEFAULT_OPT_LEVEL}).as_text()


def _tpu_plan(precision="dd", dtype=jnp.float32, **kw):
    return gemm.make_plan(N, N, N, dtype=dtype, precision=precision,
                          platform="tpu", use_cache=False, **kw)


# ---------------------------------------------------------------------------
# compiles (about two seconds each)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("precision,epilogue", [
    pytest.param("dd", "none", id="none"),
    pytest.param("dd", "full", id="full"),
    pytest.param("td", "full", id="td-full"),
])
def test_ozgemm_kernel_compiles_at_tpu_plan_blocks(one_chip, precision,
                                                   epilogue):
    """"full" is the fused alpha/beta drain of the LU's trailing update;
    at td it is the program the LU cell runs on the chip."""
    from repro.kernels.ozgemm import ozgemm_kernel_call

    plan = _tpu_plan(precision)
    nl = mp.PRECISIONS[precision]
    operands = _limbs((256, 256), 2 * nl, one_chip)
    if epilogue == "full":
        operands += _limbs((1, 1), 2 * nl, one_chip) + _limbs((256, 256), nl,
                                                              one_chip)
    txt = _compile(
        lambda *l: ozgemm_kernel_call(
            *l, bm=plan.bm, bn=plan.bn, bk=plan.bk, beta=plan.slice_beta,
            n_slices=plan.n_slices, slice_dtype_name=plan.slice_dtype,
            acc_dtype_name=plan.acc_dtype, epilogue=epilogue,
            interpret=False),
        *operands)
    assert "tpu_custom_call" in txt


def test_mlgemm_kernel_compiles_at_tpu_plan_blocks(one_chip):
    from repro.kernels.mlgemm import mlgemm_kernel_call

    plan = _tpu_plan(backend="pallas")
    txt = _compile(
        lambda *l: mlgemm_kernel_call(*l, bm=plan.bm, bn=plan.bn,
                                      bk=plan.bk, interpret=False),
        *_limbs((256, 256), 4, one_chip))
    assert "tpu_custom_call" in txt


def test_default_tpu_plan_compiles_at_n4096(one_chip):
    plan = _tpu_plan()
    assert plan.backend == "ozaki-pallas" and not plan.interpret
    assert (plan.bm, plan.bn, plan.bk) == (128, 128, 128)

    def run(*l):
        return mp.limbs(engine._execute_2d(plan, mp.from_limbs(l[:2]),
                                           mp.from_limbs(l[2:])))

    assert "tpu_custom_call" in _compile(run, *_limbs((N, N), 4, one_chip))


def test_summa_runner_compiles_on_2x2_mesh(topo):
    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("rows", "cols"))
    n = 1024  # 512^2 per chip: the smallest that keeps 128^3 blocks
    plan = gemm.make_plan(n, n, n, dtype=jnp.float32, platform="tpu",
                          mesh=mesh, use_cache=False)
    run, (mpad, npad, kpad) = engine._summa_runner(plan, n, n, n, 2)
    blk = NamedSharding(mesh, P(plan.shard_axis, plan.shard_axis_n))
    txt = _compile(run, *_limbs((mpad, kpad), 2, blk),
                   *_limbs((kpad, npad), 2, blk),
                   *_limbs((mpad, npad), 2, blk))
    assert "collective-permute" in txt
    assert "tpu_custom_call" in txt


def _lu_solve(lu, b):
    piv = jnp.zeros(lu[0].shape[0], jnp.int32)
    return mp.limbs(lu_solve(mp.from_limbs(lu), piv, mp.from_limbs(b)))


F64_ENTRIES = {
    "lu_solve": _lu_solve,
    "rgetrf": lambda a, _: mp.limbs(rgetrf(mp.from_limbs(a), 512)[0]),
    "rpotrf": lambda a, _: mp.limbs(rpotrf(mp.from_limbs(a))),
    "mp.mul": lambda a, b: mp.limbs(mp.mul(mp.from_limbs(a),
                                           mp.from_limbs(b))),
}


@pytest.mark.parametrize("entry", sorted(F64_ENTRIES))
def test_f64_limb_arithmetic_refused_when_lowered_for_tpu(one_chip, entry):
    """Paths that plan no GEMM meet the f64 refusal where they lower."""
    a = _limbs((256, 256), 2, one_chip, jnp.float64)
    with pytest.raises(ValueError) as e:
        jax.jit(F64_ENTRIES[entry]).lower(a, a)
    assert str(e.value).startswith(TPU_F64_REFUSAL)


# ---------------------------------------------------------------------------
# the TPU support matrix, decided at plan time (no compile)
# ---------------------------------------------------------------------------

TIERS = ("dd", "td", "qd")
BACKENDS = ("ozaki-pallas", "pallas", "ozaki", "xla", "ref")
PLANNED = [(be, p) for be in ("ozaki-pallas", "pallas") for p in TIERS] \
    + [("ozaki", "dd"), ("ozaki", "td")]
_NO_QD = "backend 'ozaki' has no qd tier"  # on every platform
REFUSED = [
    pytest.param(be, p, dt, reason, id=f"{be}-{p}-{dt}")
    for dt in ("float64", "float32") for be in BACKENDS for p in TIERS
    for reason in [_NO_QD if (be, p) == ("ozaki", "qd")
                   else TPU_F64_REFUSAL if dt == "float64"
                   else TPU_REF_REFUSAL if be == "ref"
                   else TPU_XLA_REFUSAL if be == "xla" else None]
    if reason is not None]


@pytest.mark.parametrize("backend,precision", PLANNED)
def test_tpu_plans_f32_limb_cells(backend, precision):
    plan = _tpu_plan(precision, backend=backend)
    assert plan.backend == backend and not plan.interpret
    if backend in ("pallas", "ozaki-pallas"):
        assert plan.bk % 128 == 0 and plan.bn % 128 == 0


@pytest.mark.parametrize("backend,precision,dtype,reason", REFUSED)
def test_tpu_refuses_at_plan_time(backend, precision, dtype, reason):
    with pytest.raises(ValueError) as e:
        _tpu_plan(precision, dtype=dtype, backend=backend)
    assert str(e.value).startswith(reason)


def test_tpu_refuses_infeasible_slicing():
    """Whole-K slicing cannot cover dd at K=65536 with f32 accumulation;
    off the TPU that plan falls back to 'xla', which the TPU refuses."""
    with pytest.raises(ValueError, match="ozaki slicing infeasible"):
        gemm.make_plan(256, 65536, 256, dtype=jnp.float32, backend="ozaki",
                       platform="tpu", use_cache=False)


def test_tpu_refuses_misaligned_pallas_blocks():
    with pytest.raises(ValueError, match=r"\(8, 128\) tiling"):
        _tpu_plan(backend="ozaki-pallas", bk=16)
    # a block spanning its whole (small) dimension is exempt
    plan = gemm.make_plan(13, 7, 9, dtype=jnp.float32, platform="tpu",
                          use_cache=False)
    assert (plan.bm, plan.bn, plan.bk) == (16, 16, 8)
