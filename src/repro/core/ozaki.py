"""Ozaki-scheme GEMM: binary128-class matmul out of *native* GEMMs.

This is the TPU-codesign counterpart of the paper's custom binary128 MACs
(DESIGN.md §2, beyond-paper path).  The FPGA builds a wide multiplier out of
DSP blocks; the TPU's native wide-throughput unit is the MXU systolic array
(bf16 x bf16 -> f32 at 197 TFLOP/s on v5e).  The Ozaki scheme [Ozaki et al.
2012; Mukunoki et al. ICPP'21, cited by the paper] decomposes each operand
into *error-free slices* such that every slice-pair GEMM is exact in the
accumulator precision; the slice products are then recombined into a
multi-limb result.

Slice extraction per row of A / column of B (Rump/Ozaki error-free split):

    w   = 2^(ceil(log2 max|row|) + beta)        # fixed-point grid
    S   = (x + w) - w                           # top beta bits, EXACT
    x  <- x - S                                 # exact remainder

Recombination is *diagonal-grouped* (DESIGN.md §9): slice products with
equal significance level d = s + t all live on one fixed-point grid, so the
whole diagonal is summed in the native accumulator FIRST — the d+1 pair
GEMMs and their sum — and only then folded into the multi-limb result.
That cuts the number of full-matrix multi-limb adds from ~s^2/2 (one per
slice pair) to s (one per diagonal): on CPU at n=256 the dd recombination
drops from 21 `dd.add` passes over HBM-resident matrices to 6 cheap
`add_float` folds, a measured ~3x end-to-end win (BENCH_GEMM.json).

Exactness condition, grouped form: a diagonal sums up to n_slices pair
products of k terms each, so

    2*beta + ceil(log2 k) + ceil(log2 n_slices) <= p_acc

guarantees every partial sum of the diagonal — inside each pair dot and
across the d+1 dot results — is exactly representable (all summands are
integer multiples of the diagonal's common grid and the running sum never
exceeds 2^p_acc grid units — true in any summation order, so XLA/MXU
reduction trees are covered).  ``slice_params`` solves this fixpoint (beta
depends on n_slices, n_slices on beta) once; the GEMM plan layer calls it
and carries (beta, n_slices) so kernels never re-derive slice parameters.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from . import dd, efts, mp

__all__ = ["ozaki_gemm", "slab_update", "slice_count", "slice_bits",
           "slice_params", "platform_dtypes"]


def platform_dtypes(platform: str):
    """(slice_dtype, acc_dtype) riding the platform's native GEMM unit.

    TPU: bf16 slices accumulated in f32 on the MXU (the beyond-paper path);
    everywhere else f64/f64, where XLA's native dot is already the fast unit.
    Consumed by the plan layer (repro.gemm.make_plan) so call sites never
    hand-pick slice dtypes.
    """
    if platform == "tpu":
        return jnp.bfloat16, jnp.float32
    return jnp.float64, jnp.float64


def slice_bits(k: int, acc_dtype, slice_dtype=None, group: int = 1) -> int:
    """Max bits per slice for exact accumulation over a k-deep GEMM.

    ``group`` is the number of same-diagonal pair products summed in the
    native accumulator before the multi-limb fold (1 = the ungrouped
    pair-at-a-time scheme); the grouped scheme needs ceil(log2 group) bits
    of extra headroom per the exactness condition in the module docstring.
    """
    p_acc = {jnp.dtype(jnp.float64): 53, jnp.dtype(jnp.float32): 24}[jnp.dtype(acc_dtype)]
    head = math.ceil(math.log2(max(k, 2)))
    if group > 1:
        head += math.ceil(math.log2(group))
    beta = (p_acc - head) // 2
    if slice_dtype is not None and jnp.dtype(slice_dtype) == jnp.dtype(jnp.bfloat16):
        beta = min(beta, 8)  # bf16 mantissa (incl. implicit bit)
    if beta < 1:
        raise ValueError(f"k={k} too deep for exact slicing in {jnp.dtype(acc_dtype).name}")
    return beta


def slice_count(target_bits: int, beta: int) -> int:
    """Slices per operand to cover target_bits of significand."""
    return math.ceil(target_bits / beta) + 1


def slice_params(k: int, acc_dtype, slice_dtype=None, *,
                 target_bits: int = 107, n_slices: int | None = None,
                 beta: int | None = None,
                 guard_bits: int = 4) -> tuple[int, int]:
    """Solve (beta, n_slices) for the diagonal-grouped scheme — the single
    source of slice parameters (``repro.gemm.make_plan`` stores the result
    on the plan; kernels consume it, never re-derive it).

    beta and n_slices are mutually dependent: summing a diagonal of up to
    n_slices pair products in the native accumulator costs ceil(log2
    n_slices) headroom bits, which shrinks beta, which raises the slice
    count needed to cover ``target_bits`` (+ log2 k for the k-fold
    truncation-error growth, + guard bits).  A short fixpoint iteration
    converges in 2-3 steps.  Either parameter may be pinned by the caller
    (the other is solved for it; pinning both is an identity).  Raises
    ValueError when k is too deep for any exact slicing in ``acc_dtype``
    (planners catch this and fall back).
    """
    need = target_bits + math.ceil(math.log2(max(k, 2))) + guard_bits
    if beta is not None:
        # pinned beta: solve (or accept) the count, then VALIDATE — a beta
        # past the grouping-headroom ceiling silently breaks the exact
        # native summation, which is the one invariant of the scheme
        s = n_slices if n_slices is not None \
            else max(2, math.ceil(need / beta))
        limit = slice_bits(k, acc_dtype, slice_dtype, group=s)
        if beta > limit:
            raise ValueError(
                f"beta={beta} violates exact accumulation for k={k}, "
                f"n_slices={s} in {jnp.dtype(acc_dtype).name} "
                f"(max {limit}: 2*beta + log2(k*n_slices) must fit p_acc)")
        return beta, s
    if n_slices is not None:
        # pinned slice count: beta just honors the grouping headroom
        return slice_bits(k, acc_dtype, slice_dtype, group=n_slices), n_slices
    s = max(2, math.ceil(need / slice_bits(k, acc_dtype, slice_dtype)))
    for _ in range(16):
        beta = slice_bits(k, acc_dtype, slice_dtype, group=s)
        s_next = max(2, math.ceil(need / beta))
        if s_next == s:
            break
        s = s_next
    return beta, s


def _extract_slices(x, beta: int, n_slices: int, axis: int):
    """Error-free slice extraction along rows (axis=1, for A) or cols (axis=0).

    Rump's ExtractVector: with row/col magnitude mu < 2^e and anchor
    sigma = 2^(e + p - beta), S = fl(r + sigma) - sigma rounds r to the grid
    2^(e+1-beta) — i.e. S carries the top ~beta bits, exactly, and r - S is
    exact.  The anchor ladder is FIXED from the initial row/col magnitude
    (sigma_i = sigma_0 * 2^(-i*beta)) rather than re-derived from each
    residual: slice i of every row then sits exactly on the grid
    2^(e+1-(i+1)*beta), so any two slice products with equal s + t share
    one fixed-point grid — the property the diagonal-grouped native
    summation's exactness proof needs (an adaptive re-anchor can drop a
    row's grid arbitrarily low after cancellation, silently widening the
    diagonal's span past p_acc).  Coverage is unchanged: the residual
    after i steps is < 2^(e+1-i*beta) either way.

    ``x`` is any multi-limb value (dd.DD or qd.QD — the residual
    subtraction runs in the value's own tier, so lower limbs surface in
    later slices).  Returns a list of limb-dtype matrices, each <= beta
    significant bits per entry on the per-row/col grid ladder.
    """
    lead = mp.limbs(x)[0]
    pbits = 53 if jnp.dtype(lead.dtype) == jnp.float64 else 24
    prec = mp.precision_of(x)
    mu = jnp.max(jnp.abs(lead), axis=axis, keepdims=True)
    # sigma = 2^(exponent(mu) + pbits - beta), built from exact
    # power-of-two primitives (xla:cpu log2/exp2 are approximate)
    sigma = _pow2_near(mu) * (2.0 ** (pbits - beta))
    nonzero = mu > 0
    slices = []
    r = x
    for _ in range(n_slices):
        hi = mp.limbs(r)[0]
        s = jnp.where(nonzero, (hi + sigma) - sigma, 0.0)
        slices.append(s)
        r = mp.sub(r, mp.from_float(s, prec))
        sigma = sigma * (2.0 ** -beta)
    return slices


def _pow2_near(mu):
    """Exact power of two just above mu: 2^(floor(log2 mu) + 1)."""
    # the floor keeps the search off zero without ever over/underflowing
    # the limb dtype (2^-511 is not representable in f32)
    floor = 2.0 ** -511 if jnp.dtype(mu.dtype) == jnp.float64 else 2.0 ** -63
    return 2.0 * efts.pow2_floor(jnp.maximum(mu, floor))


def _diagonal_pairs(d: int, n_slices: int):
    """(s, t) slice pairs on diagonal d = s + t, most-significant A first."""
    return [(i, d - i) for i in range(max(0, d - n_slices + 1),
                                      min(d + 1, n_slices))]


def _normalize_slices(slices, beta: int, axis: int, slice_dtype):
    """Ladder-normalize slices into a narrow dtype, EXACTLY.

    Slice i is scaled by 2^(i*beta) / sc — the inverse of its own rung of
    the extraction ladder — so every slice lands at O(1) per row/col
    regardless of how deep the ladder goes (a single shared scale would
    leave slice i at relative magnitude 2^(-i*beta), which underflows
    bf16/f32 for the qd-depth ladders).  All factors are exact powers of
    two, so grid alignment survives: the product of A-slice s and B-slice
    t carries the residual factor 2^(-(s+t)*beta), i.e. one rescale of
    sc_a * sc_b * 2^(-d*beta) per DIAGONAL, which is what lets a whole
    diagonal still accumulate natively and rescale once.

    Returns (scaled slices, sc).
    """
    sc = _pow2_near(jnp.max(jnp.abs(slices[0]), axis=axis, keepdims=True))
    return [((s * (2.0 ** (i * beta))) / sc).astype(slice_dtype)
            for i, s in enumerate(slices)], sc


def _fold_diagonal(acc, dsum):
    """acc += one diagonal's native-dtype sum, in acc's own tier.

    dd keeps its cheap ``add_float`` fold; wider counts distill the
    (k+1)-term list — cheaper than lifting ``dsum`` to a full tier add.
    A sum wider than the limbs (f64 accumulation into f32 limbs) is split
    off exactly into a second limb first, so the narrowing loses nothing.
    """
    limb_dtype = mp.limbs(acc)[0].dtype
    k = mp.nlimbs(acc)
    if dsum.dtype == limb_dtype:
        if isinstance(acc, dd.DD):
            return dd.add_float(acc, dsum)
        parts = [dsum]
    else:
        hi = dsum.astype(limb_dtype)
        lo = (dsum - hi.astype(dsum.dtype)).astype(limb_dtype)
        if isinstance(acc, dd.DD):
            return dd.add(acc, dd.from_hi_lo(hi, lo))
        parts = [hi, lo]
    return mp.from_limbs(
        mp.renorm_list(list(mp.limbs(acc)) + parts, k=k, sweeps=3))


def slab_update(acc, a, b, *, beta: int, n_slices: int, slice_dtype,
                acc_dtype, full: bool):
    """acc += A @ B by error-free slicing — the recombination loop shared
    by the whole-K path and the fused kernel's per-K-slab step.

    Diagonal-grouped, most-significant diagonal first: the d+1 pair dots
    of diagonal d sum in ``acc_dtype`` — exact by the slice_params
    headroom — then ONE multi-limb fold per diagonal instead of one per
    slice pair.  (Separate pair dots beat one concatenated (m,(d+1)k) dot
    on xla:cpu by ~2.5x: the concat copies defeat the contraction's fast
    path; the summation is exact either way.)
    """
    limb_dtype = mp.limbs(a)[0].dtype
    sa = _extract_slices(a, beta, n_slices, axis=1)
    sb = _extract_slices(b, beta, n_slices, axis=0)
    narrow = jnp.dtype(slice_dtype) != jnp.dtype(limb_dtype)
    if narrow:
        # exact ladder normalization into the narrow dtype; pair (s, t)
        # then carries the residual factor 2^(-(s+t)*beta), one rescale
        # per diagonal, applied in the wider of the accumulator and limb
        # dtypes so no bit of the exact diagonal sum is rounded away
        sa, sc_a = _normalize_slices(sa, beta, 1, slice_dtype)
        sb, sc_b = _normalize_slices(sb, beta, 0, slice_dtype)
        wide = jnp.promote_types(acc_dtype, limb_dtype)
        sc = sc_a.astype(wide) * sc_b.astype(wide)
    n_diag = (2 * n_slices - 1) if full else n_slices
    for d in range(n_diag):
        dsum = None
        for s, t in _diagonal_pairs(d, n_slices):
            p = jnp.dot(sa[s], sb[t],
                        preferred_element_type=jnp.dtype(acc_dtype))
            dsum = p if dsum is None else dsum + p
        if narrow:
            dsum = dsum.astype(wide) * (sc * (2.0 ** (-d * beta)))
        acc = _fold_diagonal(acc, dsum)
    return acc


@partial(jax.jit, static_argnames=("slice_dtype_name", "acc_dtype_name",
                                   "n_slices", "beta", "full"))
def _ozaki_impl(*ab_limbs, slice_dtype_name: str,
                acc_dtype_name: str, n_slices: int, beta: int, full: bool):
    nlimbs = len(ab_limbs) // 2
    a = mp.from_limbs(ab_limbs[:nlimbs])
    b = mp.from_limbs(ab_limbs[nlimbs:])
    m, n = ab_limbs[0].shape[0], ab_limbs[nlimbs].shape[1]
    acc = mp.zeros((m, n), mp.precision_of(a), dtype=ab_limbs[0].dtype)
    acc = slab_update(acc, a, b, beta=beta, n_slices=n_slices,
                      slice_dtype=jnp.dtype(slice_dtype_name),
                      acc_dtype=jnp.dtype(acc_dtype_name), full=full)
    return tuple(mp.limbs(acc))


def ozaki_gemm(a, b, *, slice_dtype=None, acc_dtype=None,
               n_slices: int | None = None, beta: int | None = None,
               target_bits: int = 107, full: bool = False):
    """C = A @ B via error-free slicing onto native GEMMs.

    ``a``/``b`` may carry any registered limb count (the slice ladder just
    runs deeper for wider tiers; the default ``target_bits`` covers dd —
    pass the tier's own target, e.g. 159 for td, for wider operands).
    Defaults: f64 slices + f64 accumulation (CPU validation path).  On TPU
    pass slice_dtype=jnp.bfloat16, acc_dtype=jnp.float32 to ride the MXU.
    When called through the engine, (beta, n_slices) come from the plan
    (``make_plan`` solved them via ``slice_params``); standalone callers
    get them solved here, once.
    """
    acc_dtype = acc_dtype or jnp.float64
    slice_dtype = slice_dtype or jnp.float64
    k = mp.limbs(a)[0].shape[1]
    beta, n_slices = slice_params(k, acc_dtype, slice_dtype,
                                  target_bits=target_bits,
                                  n_slices=n_slices, beta=beta)
    out = _ozaki_impl(
        *mp.limbs(a), *mp.limbs(b),
        slice_dtype_name=jnp.dtype(slice_dtype).name,
        acc_dtype_name=jnp.dtype(acc_dtype).name,
        n_slices=n_slices, beta=beta, full=full,
    )
    return mp.from_limbs(out)
