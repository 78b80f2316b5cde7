"""Planning layer: one decision point for every extended-precision GEMM.

The paper's FPGA fixes its execution configuration (PE-array shape, M_Tile,
operand format) at synthesis time; every GEMM then streams through that one
design.  ``GemmPlan`` is the runtime analogue: a frozen record of every
choice the engine needs — backend, block shapes, limb dtype, interpret mode,
batch strategy, and an optional mesh shard spec for the multi-device SUMMA
distribution —
produced once by ``make_plan`` from the problem shape and platform, then
handed to ``engine.execute``.  The shard spec is 2-D: ``shard_axis`` /
``shard_axis_n`` name the mesh axes carrying C's row / column blocks (named
through ``runtime.sharding``'s logical-axis rule tables) and ``k_panel``
fixes the depth of the A/B panels the SUMMA loop broadcasts per K-step —
the software analogue of the paper's DDR→BRAM panel streaming granularity.

Block shapes resolve in priority order: explicit overrides > tuned entries
from the on-disk cache (written by ``autotune``) > the clamped heuristic
``DEFAULT_BLOCKS`` defined below (and re-exported by ``kernels.ddgemm``
for kernel-level callers).
"""

from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.efts import TPU_F64_REFUSAL
# the arithmetic layer owns the tier -> limb-count map (re-exported below
# so plan consumers need not import core); also enables x64 on import
from repro.core.mp import PRECISIONS
from repro.runtime.faults import BackendFailoverWarning

from . import cache as plan_cache

__all__ = ["GemmPlan", "make_plan", "replan_precision", "resolve_backend",
           "round_up", "BACKENDS", "PRECISIONS", "DEFAULT_BLOCKS",
           "OZAKI_TARGET_BITS", "ozaki_target_bits", "TPU_PALLAS_BLOCKS",
           "FALLBACK_CHAINS", "fallback_chain"]

BACKENDS = ("auto", "pallas", "ozaki", "ozaki-pallas", "xla", "ref")

# guarded-execution levels (mirrored by gemm.guard.CHECKS; defined here so
# plan validation does not import the guard module, which imports us)
_CHECK_LEVELS = ("none", "finite", "full")

# declared failover order per backend, most- to least-specialized.  The
# chain ends at 'xla' (pure jnp — if that fails, the failure is in the
# operands or JAX itself, and failover would only mask it); 'ref' is an
# oracle, not a production fallback.  The engine walks this chain when a
# backend raises at compile/run time, quarantining each failed rung; the
# planner consults the same chain to skip quarantined backends at plan
# time.
FALLBACK_CHAINS = {
    "ozaki-pallas": ("ozaki", "xla"),
    "pallas": ("xla",),
    "ozaki": ("xla",),
    "xla": (),
    "ref": (),
}


def fallback_chain(backend: str, precision: str = "dd"):
    """The failover chain for a backend, tier-filtered.

    The whole-K 'ozaki' path has no qd tier, so qd plans skip that rung
    (make_plan would reject it; the engine must not fail over into a
    ValueError).
    """
    chain = FALLBACK_CHAINS.get(backend, ())
    if precision == "qd":
        chain = tuple(b for b in chain if b != "ozaki")
    return chain

# backends that decompose operands into error-free slices; their plans
# carry solved (slice_beta, n_slices) so kernels never re-derive them
_SLICED_BACKENDS = ("ozaki", "ozaki-pallas")

# default significand coverage per tier for the slicing backends over f64
# limbs: dd is binary128-class (the paper's format), td the 3-limb
# ~159-bit middle rung, qd the 4-limb ~212-bit tier.  Over f32 limbs each
# tier covers its own k*24+1 bits (see ozaki_target_bits)
OZAKI_TARGET_BITS = {"dd": 107, "td": 159, "qd": 212}


def ozaki_target_bits(precision: str, limb_dtype) -> int:
    """Significand bits a sliced product must cover for a tier's limbs."""
    if jnp.dtype(limb_dtype) == jnp.float64:
        return OZAKI_TARGET_BITS[precision]
    return PRECISIONS[precision] * 24 + 1

# (bm, bn, bk) heuristic defaults: the "8x16 PE / M_Tile=512" analogue from
# the bench_tile sweep — VMEM cost = (bm*bk + bk*bn + 2*bm*bn) * 2 limbs * 4B.
# Owned by the plan layer (tile choice is a planning concern); the Pallas
# kernel module re-exports it so kernel-level callers keep working without
# this module importing pallas eagerly.
DEFAULT_BLOCKS = {"bm": 128, "bn": 128, "bk": 16}

# Mosaic tiles every 32-bit block as (8, 128): the last dim of each Pallas
# block (bk of A's, bn of B's and C's) must be a multiple of 128, the one
# before it a multiple of 8, unless the block spans the whole (padded)
# array.  On the TPU the Pallas backends default to this aligned depth.
TPU_PALLAS_BLOCKS = {"bm": 128, "bn": 128, "bk": 128}
_PALLAS_BACKENDS = ("pallas", "ozaki-pallas")

_ENV_BACKEND = "REPRO_GEMM_BACKEND"
_DEFAULT_BACKEND = "ozaki"
# on the TPU the default rides the MXU through the fused slicing kernel
_DEFAULT_BACKEND_TPU = "ozaki-pallas"

# What the TPU refuses, found by compiling for a described v5e and by the
# f64 probe of chip_smoke.py; make_plan raises these before anything is
# traced, so no refused pair reaches the engine's failover walk.  (f64
# limbs are refused again wherever an EFT is lowered for the TPU, which
# covers the paths that plan no GEMM: see core.efts.)
TPU_REF_REFUSAL = (
    "backend 'ref' is refused on the TPU: the oracle holds all m*k*n tier "
    "products at once and exists to check other backends on the CPU")
TPU_XLA_REFUSAL = (
    "backend 'xla' is refused on the TPU: its tier dot materializes "
    "(m, bk, n) tier products in HBM, and at n=4096 the TPU compiler asks "
    "5.1 GB for dd, 15.6 GB for td and 23.3 GB for qd of a v5e's 15.75 GB; "
    "use ozaki-pallas or pallas")


@dataclasses.dataclass(frozen=True)
class GemmPlan:
    """Everything ``engine.execute`` needs to run one GEMM workload."""

    backend: str                      # pallas | ozaki | xla | ref
    bm: int                           # pallas M-tile; also clamps batched calls
    bn: int                           # pallas N-tile
    bk: int                           # pallas K-tile / xla K-chunk
    limb_dtype: str                   # 'float64' (dd64) | 'float32' (df32)
    interpret: bool                   # pallas interpret mode (True off-TPU)
    platform: str                     # 'cpu' | 'tpu' | 'gpu'
    precision: str = "dd"             # tier: dd (2 limbs) | td (3) | qd (4)
    batch: str = "none"               # none | vmap
    batch_shape: Tuple[int, ...] = ()
    shard_axis: Optional[str] = None  # mesh axis sharding the M (row) dim
    shard_axis_n: Optional[str] = None  # mesh axis sharding the N (col) dim
    k_panel: Optional[int] = None     # SUMMA K-panel depth (default: bk)
    comm: str = "ring"                # SUMMA panel movement: ring | psum
    k_stream: Optional[int] = None    # host-side out-of-core K chunk depth
    mesh: Any = dataclasses.field(default=None, compare=False, repr=False)
    slice_dtype: Optional[str] = None  # ozaki operand slices (bf16 on TPU)
    acc_dtype: Optional[str] = None    # ozaki accumulator (f32 on TPU)
    n_slices: Optional[int] = None     # ozaki slices per operand (solved)
    slice_beta: Optional[int] = None   # ozaki bits per slice (solved)
    target_bits: Optional[int] = None  # ozaki significand coverage target
    full: Optional[bool] = None        # ozaki: keep sub-target slice products
    check: str = "none"                # guarded execution: none|finite|full
    source: str = "heuristic"          # heuristic | tuned | override

    @property
    def blocks(self) -> dict:
        return {"bm": self.bm, "bn": self.bn, "bk": self.bk}

    @property
    def nlimbs(self) -> int:
        return PRECISIONS[self.precision]

    def with_(self, **changes) -> "GemmPlan":
        return dataclasses.replace(self, **changes)


def resolve_backend(backend: str = "auto",
                    platform: Optional[str] = None) -> str:
    platform = platform or jax.default_backend()
    default = _DEFAULT_BACKEND_TPU if platform == "tpu" else _DEFAULT_BACKEND
    be = backend if backend != "auto" else os.environ.get(
        _ENV_BACKEND, default)
    if be not in BACKENDS or be == "auto":
        raise ValueError(f"unknown GEMM backend {be!r}; one of {BACKENDS}")
    return be


def round_up(x: int, b: int) -> int:
    return -(-x // b) * b


def _tpu_refusal(backend: str, dtype, blocks: dict, m: int, k: int,
                 n: int) -> Optional[str]:
    """Why the TPU cannot run this plan, or None when it compiles there."""
    if jnp.dtype(dtype) == jnp.float64:
        return TPU_F64_REFUSAL
    if backend == "ref":
        return TPU_REF_REFUSAL
    if backend == "xla":
        return TPU_XLA_REFUSAL
    if backend in _PALLAS_BACKENDS:
        # a block that spans its whole padded dimension is exempt from
        # the (8, 128) rule; any narrower block must be aligned
        bad = [f"{x}={blocks[x]}"
               for x, q, ext in (("bm", 8, m), ("bn", 128, n),
                                 ("bk", 128, k))
               if blocks[x] % q and blocks[x] < round_up(ext, 8)]
        if bad:
            return (f"Pallas blocks {', '.join(bad)} break the TPU's (8, "
                    f"128) tiling: a block narrower than its padded "
                    f"dimension needs bm % 8 == 0 and bn, bk % 128 == 0")
    return None


def _clamp_blocks(m: int, k: int, n: int, blocks: dict) -> dict:
    # tiny problems keep tiny tiles: clamp to the 8-aligned problem size so a
    # 16x16 GEMM does not pad out to a 128x128 tile.  The single clamp rule
    # for the whole package — engine/autotune import it rather than redefine.
    return {
        "bm": min(blocks["bm"], round_up(m, 8)),
        "bn": min(blocks["bn"], round_up(n, 8)),
        "bk": min(blocks["bk"], round_up(k, 8)),
    }


def make_plan(m: int, k: int, n: int, *, dtype=jnp.float64,
              precision: str = "dd",
              backend: str = "auto", batch_shape: Tuple[int, ...] = (),
              bm: Optional[int] = None, bn: Optional[int] = None,
              bk: Optional[int] = None, interpret: Optional[bool] = None,
              platform: Optional[str] = None, mesh=None,
              shard_axis: Optional[str] = None,
              shard_axis_n: Optional[str] = None,
              k_panel: Optional[int] = None,
              comm: str = "ring",
              k_stream: Optional[int] = None,
              slice_dtype=None, acc_dtype=None,
              n_slices: Optional[int] = None,
              target_bits: Optional[int] = None, full: Optional[bool] = None,
              chunk: Optional[int] = None,
              check: str = "none",
              use_cache: bool = True) -> GemmPlan:
    """Plan one GEMM workload: (batch_shape) x (m, k) @ (k, n).

    Consults the tuned-block cache for (shape-bucket, dtype, limb count,
    platform) before falling back to clamped DEFAULT_BLOCKS, so autotuned
    tiles are reused across calls and across processes — and each precision
    tier tunes its own tiles (a QD wave moves 2x the limb planes of DD).
    """
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; "
                         f"one of {sorted(PRECISIONS)}")
    if check not in _CHECK_LEVELS:
        raise ValueError(f"unknown check level {check!r}; "
                         f"one of {_CHECK_LEVELS}")
    platform = platform or jax.default_backend()
    be = resolve_backend(backend, platform)
    if precision == "qd" and be == "ozaki":
        if backend == "ozaki":
            # explicit request: fail loudly — whole-K slicing for a 212-bit
            # target makes the slice-product sweep useless (the per-slab
            # 'ozaki-pallas' kernel is the qd slicing path)
            raise ValueError(
                "backend 'ozaki' has no qd tier (slice count explodes past "
                "the 212-bit target); use ozaki-pallas, pallas, xla, or ref")
        be = "xla"  # 'auto'/env default 'ozaki' is a dd-oriented hint
    dtype = jnp.dtype(dtype)

    # quarantine consult: a backend that recently failed at compile/run
    # time on this (platform, limb count) is benched in the plan cache —
    # re-plan onto the first healthy rung of its fallback chain instead of
    # re-paying the doomed lowering attempt at execute time.  use_cache=
    # False opts out (tests and bisection need to hit the backend anyway).
    if use_cache:
        nl = PRECISIONS[precision]
        q = plan_cache.quarantined(platform, be, nl)
        if q is not None:
            for fb in fallback_chain(be, precision):
                if plan_cache.quarantined(platform, fb, nl) is None:
                    warnings.warn(
                        f"GEMM backend {be!r} is quarantined on "
                        f"{platform!r} ({q.get('reason', '?')}); planning "
                        f"onto fallback {fb!r} (repro.gemm."
                        f"clear_quarantine() lifts the bench)",
                        BackendFailoverWarning, stacklevel=2)
                    be = fb
                    break
            # every rung benched: keep the original backend and let the
            # engine's failover loop re-attempt (and re-diagnose) live

    if interpret is None:
        interpret = platform != "tpu"
    if chunk is not None:
        bk = bk or chunk  # legacy xla-backend spelling of the K block

    if mesh is not None:
        # the dormant logical-axis rule tables name the mesh axes: "gemm_m"
        # / "gemm_n" resolve against the mesh so GEMM meshes (rows/cols)
        # and production LM meshes (data/model) both work unannotated.
        # Fully-explicit axes route through the same resolver so a typo'd
        # or duplicated axis fails HERE, not deep inside shard_map
        from repro.runtime.sharding import gemm_mesh_axes

        shard_axis, shard_axis_n = gemm_mesh_axes(
            mesh, m_axis=shard_axis, n_axis=shard_axis_n)
    if mesh is None and not (shard_axis is None and shard_axis_n is None
                             and k_panel is None and k_stream is None):
        # a shard spec without a mesh would silently run unsharded — the
        # same dropped-operand failure mode the beta-without-c rule stops
        raise ValueError(
            "shard_axis/shard_axis_n/k_panel/k_stream require mesh= "
            "(without a mesh there is nothing to shard or stream over)")
    if k_panel is not None and k_panel <= 0:
        raise ValueError(f"k_panel must be positive, got {k_panel}")
    if comm not in ("ring", "psum"):
        raise ValueError(f"unknown SUMMA comm schedule {comm!r}; "
                         f"one of ('ring', 'psum')")
    if k_stream is not None and k_stream <= 0:
        raise ValueError(f"k_stream must be positive, got {k_stream}")

    # tuned blocks are looked up for the shape a device actually runs: a
    # sharded plan's per-device SUMMA panels are the (m/Pr, k, n/Pc) local
    # problem, not the global one the caller named
    m_l, n_l = m, n
    if mesh is not None:
        if shard_axis is not None:
            m_l = -(-m // mesh.shape[shard_axis])
        if shard_axis_n is not None:
            n_l = -(-n // mesh.shape[shard_axis_n])

    source = "heuristic"
    tpu_pallas = platform == "tpu" and be in _PALLAS_BACKENDS
    blocks = dict(TPU_PALLAS_BLOCKS if tpu_pallas else DEFAULT_BLOCKS)
    if use_cache and be in ("pallas", "xla", "ozaki-pallas") \
            and (bm, bn, bk) == (None,) * 3:
        key = plan_cache.cache_key(platform, dtype.name, m_l, k, n_l, be,
                                   nlimbs=PRECISIONS[precision],
                                   batch_shape=batch_shape)
        tuned = plan_cache.default_cache().get(key)
        # adopt only well-formed entries: the cache is a hint, and a bad
        # persistent value (hand-edit, corruption) must degrade to the
        # heuristic, not break every GEMM in this bucket forever
        if tuned and all(
                isinstance(tuned.get(x), int) and tuned[x] > 0
                for x in ("bm", "bn", "bk")) and not (
                tpu_pallas and _tpu_refusal(be, dtype, tuned, m_l, k, n_l)):
            blocks = {x: int(tuned[x]) for x in ("bm", "bn", "bk")}
            source = "tuned"
            # tuned n_slices was measured for the DEFAULT coverage target
            # and platform slice/acc dtypes: a caller-specified target or
            # dtype override must re-solve, not adopt it (bf16 slices cap
            # beta at 8, so an f64-tuned count would under-cover by ~70
            # bits)
            if be == "ozaki-pallas" and n_slices is None and \
                    target_bits is None and \
                    slice_dtype is None and acc_dtype is None and \
                    isinstance(tuned.get("n_slices"), int) and \
                    tuned["n_slices"] > 1:
                n_slices = tuned["n_slices"]  # tuned alongside the blocks
    blocks = _clamp_blocks(m_l, k, n_l, blocks)
    if bm or bn or bk:
        source = "override"
    blocks["bm"] = bm or blocks["bm"]
    blocks["bn"] = bn or blocks["bn"]
    blocks["bk"] = bk or blocks["bk"]
    if platform == "tpu":
        reason = _tpu_refusal(be, dtype, blocks, m_l, k, n_l)
        if reason:
            raise ValueError(reason)

    slice_beta = None
    if be in _SLICED_BACKENDS:
        from repro.core import ozaki as _ozaki

        if slice_dtype is None and acc_dtype is None:
            slice_dtype, acc_dtype = _ozaki.platform_dtypes(platform)
        target_bits = target_bits or ozaki_target_bits(precision, dtype)
        # the fused kernel slices per K-slab (depth bk), the XLA path
        # slices the whole K — the exactness fixpoint sees that depth
        depth = blocks["bk"] if be == "ozaki-pallas" else k
        try:
            slice_beta, n_slices = _ozaki.slice_params(
                depth, acc_dtype or jnp.float64, slice_dtype,
                target_bits=target_bits, n_slices=n_slices)
        except ValueError as e:
            # K too deep for exact slicing in the accumulator dtype: the
            # plan degrades to the portable blocked-XLA backend rather
            # than crashing the caller (tested in test_ozgemm_kernel.py);
            # the TPU refuses that backend, so there the plan fails
            if platform == "tpu":
                raise ValueError(
                    f"ozaki slicing infeasible for this problem ({e}), and "
                    f"the TPU refuses the 'xla' fallback; use "
                    f"ozaki-pallas, whose slab depth bk bounds the "
                    f"slicing") from e
            warnings.warn(
                f"ozaki slicing infeasible for this problem ({e}); "
                f"falling back to the 'xla' backend", RuntimeWarning,
                stacklevel=2)
            be = "xla"
            slice_dtype = acc_dtype = None
            n_slices = target_bits = None
            full = None

    return GemmPlan(
        backend=be, limb_dtype=dtype.name, interpret=bool(interpret),
        platform=platform, precision=precision,
        batch="vmap" if batch_shape else "none",
        batch_shape=tuple(batch_shape), shard_axis=shard_axis,
        shard_axis_n=shard_axis_n, k_panel=k_panel, comm=comm,
        k_stream=k_stream, mesh=mesh,
        slice_dtype=jnp.dtype(slice_dtype).name if slice_dtype else None,
        acc_dtype=jnp.dtype(acc_dtype).name if acc_dtype else None,
        n_slices=n_slices, slice_beta=slice_beta,
        target_bits=target_bits, full=full, check=check,
        source=source, **blocks)


def replan_precision(plan: GemmPlan, m: int, k: int, n: int,
                     precision: str) -> GemmPlan:
    """Re-plan the same workload at another precision tier.

    The tier-escalating refinement solver climbs the ladder mid-solve
    (f64 -> dd -> td -> qd); structural choices (backend, platform, mesh, batch
    shape) carry over, but everything tier-dependent is *re-solved* rather
    than copied — block shapes consult the new limb count's tuned-cache
    rows, and the Ozaki slice parameters re-run their exactness fixpoint
    for the new target_bits (a dd-tuned n_slices would under-cover qd by
    ~100 bits).  ``plan.with_(precision=...)`` must not exist for exactly
    that reason.  The shape is an argument because a plan does not record
    it (the paper's synthesized design is shape-free; so is ours).
    """
    if plan.precision == precision:
        return plan
    backend = plan.backend
    if backend == "ozaki" and precision == "qd":
        backend = "xla"  # the whole-K slicing path has no qd tier
    return make_plan(
        m, k, n, dtype=plan.limb_dtype, precision=precision,
        backend=backend, batch_shape=plan.batch_shape,
        interpret=plan.interpret, platform=plan.platform,
        mesh=plan.mesh, shard_axis=plan.shard_axis,
        shard_axis_n=plan.shard_axis_n, k_panel=plan.k_panel,
        comm=plan.comm, k_stream=plan.k_stream,
        check=plan.check)
