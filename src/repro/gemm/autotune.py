"""Block-shape autotuner — the runtime analogue of the paper's M_Tile sweep.

The paper sweeps the per-PE memory tile (M_Tile) and PE-array shape at
synthesis time (Fig. 3, Tables II/III) and ships the best configuration.
Here the same sweep runs once per (shape-bucket, dtype, platform) at
runtime: candidate (bm, bn, bk) tiles are filtered by the VMEM working-set
model (the hard "fits on chip" constraint), then timed on the live kernel,
and the winner is persisted via ``cache.PlanCache`` so every later call
with the same bucket reuses it instead of DEFAULT_BLOCKS.  The streaming
bandwidth model B_req (Eq. 5) is reported by ``benchmarks/bench_tile.py``
rather than used as a filter — on interpret-mode hosts wall time already
reflects the real constraint, and on TPU a bandwidth-starved tile simply
times worse.

Resource models (re-derived for the TPU port, previously inlined in
``benchmarks/bench_tile.py`` which now imports them from here):

  F_peak = peak_f32_flops / flops_per_dd_fma            (VPU path)
  B_req  = (bm + bn) / (bm * bn) * F_peak / 2 * 32 B/s  (stream A and B)
  VMEM   = 2 limbs * limb_bytes * (bm*bk + bk*bn + 2*bm*bn)
"""

from __future__ import annotations

import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mp
from . import cache as plan_cache
from .plan import GemmPlan, PRECISIONS, _clamp_blocks, make_plan, \
    resolve_backend

__all__ = [
    "autotune", "candidate_blocks", "vmem_bytes", "bandwidth_req_gbps",
    "FLOPS_PER_DD_FMA", "V5E_F32_FLOPS", "VMEM_BYTES", "HBM_GBPS",
]

# measured static op count of one DD multiply-add (two_prod + dd add chain)
FLOPS_PER_DD_FMA = 86
V5E_F32_FLOPS = 197e12 / 2   # VPU f32 is ~half the bf16 MXU rate
VMEM_BYTES = 16 * 2**20      # v5e per-core VMEM
HBM_GBPS = 819               # v5e HBM bandwidth

# sweep grid: the bench_tile shapes plus the skinny-K variants the LU
# trailing updates (k = panel width 8..64) actually hit
_SWEEP: Tuple[Tuple[int, int, int], ...] = (
    (32, 32, 8), (32, 32, 32), (64, 64, 8), (64, 64, 16), (64, 64, 32),
    (128, 128, 8), (128, 128, 16), (128, 128, 64), (128, 256, 16),
    (256, 128, 16),
)


def vmem_bytes(bm: int, bn: int, bk: int, limb_bytes: int = 4,
               nlimbs: int = 2) -> int:
    # a-tile + b-tile + 2 accumulators, one plane per limb
    return nlimbs * limb_bytes * (bm * bk + bk * bn + 2 * bm * bn)


def bandwidth_req_gbps(bm: int, bn: int, f_peak_flops: float) -> float:
    return (bm + bn) / (bm * bn) * f_peak_flops / 2 * 32 / 1e9


def f_peak_gflops() -> float:
    """Model binary128-class peak on the VPU path (GFlop/s)."""
    return V5E_F32_FLOPS / FLOPS_PER_DD_FMA / 1e9


def candidate_blocks(m: int, k: int, n: int,
                     limb_bytes: int = 4, nlimbs: int = 2) -> List[dict]:
    """Sweep candidates clamped to the problem and filtered by VMEM fit.

    The fit model scales with the limb count, so the qd tier's feasible set
    is roughly the dd set shrunk one tile size — tuned independently.
    """
    out, seen = [], set()
    for bm, bn, bk in _SWEEP:
        blk = _clamp_blocks(m, k, n, {"bm": bm, "bn": bn, "bk": bk})
        key = (blk["bm"], blk["bn"], blk["bk"])
        if key in seen:
            continue
        seen.add(key)
        if vmem_bytes(**blk, limb_bytes=limb_bytes,
                      nlimbs=nlimbs) < VMEM_BYTES:
            out.append(blk)
    return out


def _time_once(fn, warmup: int = 1, iters: int = 2) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def _expand_slice_candidates(m: int, k: int, n: int, blocks: Sequence[dict],
                             dtype, precision: str) -> List[dict]:
    """Cross block candidates with ``n_slices`` for the slicing kernel.

    Per block shape the exactness fixpoint fixes the MINIMUM slice count
    for the slab depth; the sweep also tries one extra slice (more dots,
    but finer slices sometimes win on accuracy-irrelevant grounds like
    concat sizes).  Counts below the minimum would silently lose bits, so
    they are never candidates.
    """
    out = []
    for blk in blocks:
        base = make_plan(m, k, n, dtype=dtype, precision=precision,
                         backend="ozaki-pallas", use_cache=False, **blk)
        if base.backend != "ozaki-pallas":
            continue  # slicing infeasible for this problem: plan fell back
        for ns in (base.n_slices, base.n_slices + 1):
            out.append(dict(blk, n_slices=ns))
    return out


def autotune(m: int, k: int, n: int, *, dtype=jnp.float64,
             precision: str = "dd", backend: str = "pallas",
             batch_shape: Tuple[int, ...] = (),
             candidates: Optional[Sequence[dict]] = None,
             cache: Optional[plan_cache.PlanCache] = None,
             seed: int = 0, iters: int = 2, persist: bool = True) -> GemmPlan:
    """Sweep block shapes on live data, persist the winner, return its plan.

    Returns the tuned ``GemmPlan`` for the (m, k, n) problem at the given
    precision tier; subsequent ``make_plan`` calls in the same (shape
    bucket, limb count, batch bucket) pick the entry up from the cache
    automatically.  ``batch_shape`` times the sweep on vmap-batched
    operands and persists under the batched bucket (schema v3 keys batched
    plans apart from the 2-D bucket — this is the API that populates
    them).  For ``backend="ozaki-pallas"`` the search space is block
    shapes x ``n_slices`` (never below the exactness minimum) and the
    winner's slice count is persisted alongside its blocks.
    """
    dtype = jnp.dtype(dtype)
    nlimbs = PRECISIONS[precision]
    # key the cache on the resolved name
    backend = resolve_backend(backend)
    cache = cache or plan_cache.default_cache()
    if candidates is not None:
        candidates = list(candidates)
    else:
        candidates = candidate_blocks(m, k, n, limb_bytes=dtype.itemsize,
                                      nlimbs=nlimbs)
        if backend == "ozaki-pallas":
            candidates = _expand_slice_candidates(m, k, n, candidates,
                                                  dtype, precision)
    if not candidates:
        raise ValueError(f"no feasible block candidates for {(m, k, n)}")

    from . import engine

    rng = np.random.default_rng(seed)
    batch_shape = tuple(batch_shape)
    a = mp.from_float(
        jnp.asarray(rng.random(batch_shape + (m, k)) - 0.5, dtype),
        precision)
    b = mp.from_float(jnp.asarray(rng.random((k, n)) - 0.5, dtype), precision)

    best, best_t = None, float("inf")
    for cand in candidates:
        blk = {x: cand[x] for x in ("bm", "bn", "bk")}
        plan = make_plan(m, k, n, dtype=dtype, precision=precision,
                         backend=backend, batch_shape=batch_shape,
                         use_cache=False,
                         n_slices=cand.get("n_slices"), **blk)
        t = _time_once(lambda: engine.execute(plan, a, b), iters=iters)
        if t < best_t:
            best, best_t = plan, t

    if persist:
        # the entry lands under the bucket that was actually timed: the
        # 2-D (b1) bucket by default, or the vmap-batched bucket when a
        # batch_shape was swept (cache schema v3 keys them apart — their
        # VMEM pressure differs by the batch factor)
        key = plan_cache.cache_key(best.platform, dtype.name, m, k, n,
                                   backend, nlimbs=nlimbs,
                                   batch_shape=batch_shape)
        entry = {"bm": best.bm, "bn": best.bn, "bk": best.bk,
                 "us_per_call": best_t * 1e6,
                 "bucket": plan_cache.shape_bucket(m, k, n)}
        if best.backend == "ozaki-pallas" and best.n_slices:
            entry["n_slices"] = int(best.n_slices)
        cache.put(key, entry)
    return best.with_(source="tuned")
