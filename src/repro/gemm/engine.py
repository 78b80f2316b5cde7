"""Execution engine: one dispatcher for every extended-precision GEMM.

``execute(plan, a, b)`` routes a planned workload to its backend kernel.
Operands are multi-limb struct-of-arrays values — ``dd.DD`` for the
``precision="dd"`` tier (2 limbs, binary128 class), ``td.TD`` for
``precision="td"`` (3 limbs, ~159 bits), or ``qd.QD`` for
``precision="qd"`` (4 limbs, binary128+) — and every capability of the
engine is limb-count generic:

  * **batched GEMM** — leading batch dimensions on either operand are
    flattened and vmapped over the planned 2-D kernel, so SDP's
    per-constraint ``X @ (A_j Z^-1)`` stacks run as one call instead of a
    Python loop over constraints;
  * **sharded GEMM** — with a mesh in the plan, execution is a SUMMA-style
    2-D distribution via ``shard_map``: C's row blocks shard over
    ``plan.shard_axis``, its column blocks over ``plan.shard_axis_n``, and
    a ``lax.fori_loop`` walks the K dimension in ``k_panel``-deep steps,
    replicating the owning device's A row-panel along the column axis and
    B column-panel along the row axis per step and accumulating into a
    local C' block in tier arithmetic.  Panel movement is a double-
    buffered ``lax.ppermute`` ring by default (``plan.comm="ring"``: the
    next step's panels travel hop-by-hop while the current dot runs; the
    loop is seeded by pre-rotating panel 0 — Cannon-style starting
    alignment), with the legacy exact masked-psum broadcast selectable as
    ``comm="psum"``; the two schedules are bit-identical.  This is the
    software analogue of the paper's DDR→BRAM panel streaming; the output
    *stays* 2-D block-sharded (``P(axis_m, axis_n)``) — no all-gather on
    the result, matching the paper's Feed/Drain streaming where C' tiles
    drain independently.  A 1-axis mesh degenerates to the old
    row-sharded layout, batched + sharded calls compose ``vmap`` outside
    the ``shard_map``, and ``plan.k_stream`` adds host-side out-of-core K
    streaming on top (chunks of A/B feed through the runner while the C'
    accumulator stays device-resident — bit-identical to the unstreamed
    run).

Backend kernels per tier: the count-generic Pallas systolic tile
(``kernels/mlgemm.py`` — one tile schedule, ``nlimbs`` limb planes), the
fused Ozaki-slice Pallas kernel (``kernels/ozgemm.py`` — every tier,
slice-pair dots on the matrix unit with in-VMEM recombination), the
blocked-XLA fallbacks, the O(m*k*n) oracles, and — dd/td only — the
whole-K Ozaki slicing path.  Padding to block multiples is exact in multi-limb
arithmetic (zeros carry no rounding), so the engine owns all
pad/clamp/slice logic.

The engine also owns the Rgemm **alpha/beta epilogue**: ``execute``/
``matmul`` accept optional ``alpha``/``beta``/``c`` operands.  On the
``ozaki-pallas`` 2-D path the epilogue is fused into the kernel's drain
step (the C' tile is scaled and combined before it leaves VMEM); every
other path applies the identical tier arithmetic as a post-step, so
results match cell-for-cell across backends.
"""

from __future__ import annotations

import functools
import math
import os
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import mp
from repro.runtime import faults as _faults
from repro.runtime.faults import (BackendExecutionError,
                                  BackendFailoverWarning,
                                  NumericalHazardError)

from . import cache as plan_cache
from . import guard
from .plan import (GemmPlan, fallback_chain, make_plan,
                   round_up as _round_up)

__all__ = ["execute", "matmul"]

# kill switch for backend failover: REPRO_GEMM_FAILOVER=0 makes a backend
# failure raise immediately (bisection wants the original traceback, not a
# masked recovery)
_ENV_FAILOVER = "REPRO_GEMM_FAILOVER"


def _pad_to(x, rows, cols):
    r, c = x.shape[-2:]
    if r == rows and c == cols:
        return x
    pad = [(0, 0)] * (x.ndim - 2) + [(0, rows - r), (0, cols - c)]
    return jnp.pad(x, pad)


def _pad(x, rows, cols):
    r, c = x.shape[-2:]
    if r == rows and c == cols:
        return x
    padded = mp.map_limbs(lambda l: _pad_to(l, rows, cols), x)
    # the barrier pins the padded limbs as opaque runtime values.  Without
    # it, operands that are trace-time CONSTANTS under an outer jit lose
    # bit-reproducibility: XLA's constant folder refuses to fold through
    # the output-enlarging pad, and the surviving constant-fed fusions
    # rewrite the downstream error-free-transformation chains
    # value-changingly (~1e-17 relative drift vs the same call un-jitted,
    # first seen on interpret-mode ozaki-pallas).  Pinning the pad output
    # makes the compiled graph per-op-faithful, so jit(const-closure),
    # jit(args), and eager all produce identical limbs.
    return mp.from_limbs(jax.lax.optimization_barrier(
        tuple(mp.limbs(padded))))


# --------------------------------------------------------------------------
# 2-D backend dispatch
# --------------------------------------------------------------------------


def _execute_pallas(plan: GemmPlan, a, b):
    from .plan import _clamp_blocks

    m, k = a.shape
    _, n = b.shape
    # re-clamp against the *actual* shapes: sharded execution hands each
    # device a row panel smaller than the global problem the plan saw
    blk = _clamp_blocks(m, k, n, plan.blocks)
    bm, bn, bk = blk["bm"], blk["bn"], blk["bk"]
    mpad, npad, kpad = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    a_p, b_p = _pad(a, mpad, kpad), _pad(b, kpad, npad)
    from repro.kernels.mlgemm import mlgemm_kernel_call

    out = mlgemm_kernel_call(*mp.limbs(a_p), *mp.limbs(b_p),
                             bm=bm, bn=bn, bk=bk,
                             interpret=plan.interpret)
    return mp.from_limbs([o[:m, :n] for o in out])


def _ozaki_pallas_params(plan: GemmPlan, bk: int):
    """(beta, n_slices, slice_dtype_name, acc_dtype_name) for a slab depth.

    The plan solved (beta, n_slices) for its own bk; a re-clamped smaller
    slab only gains exactness headroom, so the planned values stay valid.
    Hand-built plans without solved parameters get them solved here.
    """
    from repro.core import ozaki as _ozaki

    slice_dtype = jnp.dtype(plan.slice_dtype) if plan.slice_dtype \
        else jnp.float64
    acc_dtype = jnp.dtype(plan.acc_dtype) if plan.acc_dtype else jnp.float64
    beta, n_slices = plan.slice_beta, plan.n_slices
    if beta is None or n_slices is None:
        from .plan import ozaki_target_bits

        beta, n_slices = _ozaki.slice_params(
            bk, acc_dtype, slice_dtype,
            target_bits=plan.target_bits or ozaki_target_bits(
                plan.precision, plan.limb_dtype),
            n_slices=n_slices, beta=beta)
    return beta, n_slices, slice_dtype.name, acc_dtype.name


def _execute_ozaki_pallas(plan: GemmPlan, a, b, alpha=None, beta=None,
                          c=None):
    """The fused Ozaki-slice kernel, optionally with the in-drain epilogue."""
    from .plan import _clamp_blocks
    from repro.kernels.ozgemm import ozgemm_kernel_call

    _faults.poke("backend.ozaki-pallas")

    m, k = a.shape
    _, n = b.shape
    blk = _clamp_blocks(m, k, n, plan.blocks)
    bm, bn, bk = blk["bm"], blk["bn"], blk["bk"]
    sbeta, n_slices, sdt, adt = _ozaki_pallas_params(plan, bk)
    mpad, npad, kpad = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    operands = list(mp.limbs(_pad(a, mpad, kpad)))
    operands += list(mp.limbs(_pad(b, kpad, npad)))
    epilogue = "none"
    if alpha is not None:
        epilogue = "alpha" if c is None else "full"
        operands += [l.reshape(1, 1) for l in mp.limbs(alpha)]
        if c is not None:
            operands += [l.reshape(1, 1) for l in mp.limbs(beta)]
            operands += list(mp.limbs(_pad(c, mpad, npad)))
    out = ozgemm_kernel_call(*operands, bm=bm, bn=bn, bk=bk, beta=sbeta,
                             n_slices=n_slices, slice_dtype_name=sdt,
                             acc_dtype_name=adt, epilogue=epilogue,
                             full=bool(plan.full),
                             interpret=plan.interpret)
    return mp.from_limbs([o[:m, :n] for o in out])


def _execute_2d(plan: GemmPlan, a, b):
    if plan.backend == "ozaki-pallas":
        return _execute_ozaki_pallas(plan, a, b)  # pokes its own site
    # chaos hook: a "backend.<name>" injection models this kernel failing
    # to lower/run (fires at trace time, so failed traces are never cached)
    _faults.poke("backend." + plan.backend)
    if plan.backend == "pallas":
        return _execute_pallas(plan, a, b)
    if plan.backend == "ozaki":
        if plan.precision == "qd":
            raise ValueError("ozaki backend has no qd tier (make_plan "
                             "should have rerouted or rejected this plan)")
        from repro.core.ozaki import ozaki_gemm

        kw = {}
        if plan.slice_dtype:
            kw["slice_dtype"] = jnp.dtype(plan.slice_dtype)
        if plan.acc_dtype:
            kw["acc_dtype"] = jnp.dtype(plan.acc_dtype)
        if plan.n_slices is not None:
            kw["n_slices"] = plan.n_slices
        if plan.slice_beta is not None:
            kw["beta"] = plan.slice_beta
        if plan.target_bits is not None:
            kw["target_bits"] = plan.target_bits
        elif plan.precision != "dd":
            # hand-built plan without a solved target: cover the tier's own
            # significand, not ozaki_gemm's dd-oriented default
            from .plan import ozaki_target_bits

            kw["target_bits"] = ozaki_target_bits(plan.precision,
                                                  plan.limb_dtype)
        if plan.full is not None:
            kw["full"] = plan.full
        return ozaki_gemm(a, b, **kw)
    if plan.backend == "xla":
        from repro.kernels.ops import matmul_ml_xla

        return matmul_ml_xla(a, b, chunk=plan.bk)
    if plan.backend == "ref":
        from repro.kernels.ref import mlgemm_ref

        return mlgemm_ref(a, b)
    raise ValueError(f"unknown backend in plan: {plan.backend!r}")


# --------------------------------------------------------------------------
# batched execution (leading batch dims -> vmap over the planned kernel)
# --------------------------------------------------------------------------


def _execute_batched(plan: GemmPlan, a, b, inner=None):
    """vmap ``inner`` (default: the planned 2-D kernel) over batch dims.

    ``inner`` is the per-matrix execution body; the sharded path passes the
    SUMMA ``shard_map`` runner here, composing vmap *outside* the shard_map
    so batched + sharded is one call (shard_map has a batching rule).
    """
    inner = inner or (lambda x, y: _execute_2d(plan, x, y))
    a_batch = a.shape[:-2]
    b_batch = b.shape[:-2]
    batch = jnp.broadcast_shapes(a_batch, b_batch)
    nb = math.prod(batch)

    def flat(x, had_batch):
        if not had_batch:
            return x
        tgt = batch + x.shape[-2:]
        return mp.map_limbs(
            lambda l: jnp.broadcast_to(l, tgt).reshape((nb,) + l.shape[-2:]),
            x)

    af = flat(a, bool(a_batch))
    bf = flat(b, bool(b_batch))
    # DD/QD are NamedTuple pytrees: in_axes=0 maps every limb plane
    fn = jax.vmap(inner,
                  in_axes=(0 if a_batch else None, 0 if b_batch else None))
    out = fn(af, bf)
    m, n = out.shape[-2:]
    return mp.map_limbs(lambda l: l.reshape(batch + (m, n)), out)


# jit wrappers keyed on the (frozen, hashable) plan: without these, every
# eager call re-traces the backend's scan/vmap/pallas graph — at the qd
# tier that retrace is thousands of ops and dominates wall time (observed
# in the SDP inner loop).  The alpha/beta/c epilogue operands ride inside
# the same jit (None is an empty pytree, so epilogue-free calls compile
# their own specialization): an eager post-step epilogue at the qd tier is
# hundreds of per-limb ops per call, which dominated the refinement
# solver's residual r = b - A x.  The mesh field is excluded from plan
# equality/hash, so only the mesh-free paths go through here; sharded
# execution compiles inside shard_map as before.


# Each wrapper returns ``(out, flags)``: the guard's hazard flags are a
# few extra reductions traced into the SAME compiled graph (``check`` is a
# static key, so unguarded calls compile flag-free specializations).  One
# dispatch total — this is what keeps check="finite" inside its ≤15%
# overhead budget; a separate probe dispatch would double the fixed cost
# on small cells.


@functools.partial(jax.jit, static_argnames=("plan", "check"))
def _execute_2d_jit(a, b, alpha, beta, c, *, plan: GemmPlan,
                    check: str = "none"):
    out = _apply_epilogue(_execute_2d(plan, a, b), alpha, beta, c)
    return out, guard.hazard_flags(plan, a, b, c, out, alpha, beta, check)


@functools.partial(jax.jit, static_argnames=("plan", "check"))
def _execute_batched_jit(a, b, alpha, beta, c, *, plan: GemmPlan,
                         check: str = "none"):
    out = _apply_epilogue(_execute_batched(plan, a, b), alpha, beta, c)
    return out, guard.hazard_flags(plan, a, b, c, out, alpha, beta, check)


@functools.partial(jax.jit, static_argnames=("plan", "check"))
def _execute_fused_alpha_jit(a, b, alpha, *, plan: GemmPlan,
                             check: str = "none"):
    out = _execute_ozaki_pallas(plan, a, b, alpha=alpha)
    return out, guard.hazard_flags(plan, a, b, None, out, alpha, None,
                                   check)


@functools.partial(jax.jit, static_argnames=("plan", "check"))
def _execute_fused_full_jit(a, b, alpha, beta, c, *, plan: GemmPlan,
                            check: str = "none"):
    out = _execute_ozaki_pallas(plan, a, b, alpha=alpha, beta=beta, c=c)
    return out, guard.hazard_flags(plan, a, b, c, out, alpha, beta, check)


# --------------------------------------------------------------------------
# alpha/beta epilogue (paper Eq. 1, host side of the Rgemm split)
# --------------------------------------------------------------------------


def _as_scalar(x, precision: str, dtype):
    """Coerce a python float / multi-limb scalar to the operands' tier."""
    try:
        return mp.promote(x, precision)
    except TypeError:
        return mp.from_float(jnp.asarray(x, dtype), precision)


def _static_zero(x) -> bool:
    """True iff ``x`` is *statically known* to be zero.

    Python numbers answer directly; concrete arrays / multi-limb scalars
    are inspected limb-wise.  A traced value answers False — it may still
    be zero at runtime, which the ``where``-guard in ``_apply_epilogue``
    (and the fused kernel drain) handles without reading C's values.
    """
    if x is None:
        return False
    if isinstance(x, (int, float)):
        return x == 0
    try:
        ls = mp.limbs(x)
    except TypeError:
        ls = [x]
    try:
        import numpy as np

        return all(not np.any(np.asarray(l)) for l in ls)
    except (jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError):
        return False


def _apply_epilogue(out, alpha, beta, c):
    """out = alpha * out [+ beta * c] in the operands' own tier — the
    post-step form, numerically identical to the kernel-fused drain.

    BLAS semantics: ``beta == 0`` means C is **not read** — a NaN/Inf in C
    must not leak through ``0 * C``.  Statically-zero betas never reach
    here (``execute`` drops C outright); a *traced* beta that is zero at
    runtime is handled by masking the ``beta * C`` term with a select, so
    the NaN produced by ``0 * NaN`` is discarded, not propagated.
    """
    if alpha is not None:
        out = mp.mul(mp.broadcast_to(alpha, out.shape), out)
    if c is not None:
        bc = mp.mul(mp.broadcast_to(beta, c.shape), c)
        bc = mp.where(jnp.broadcast_to(mp.is_zero(beta), bc.shape),
                      mp.map_limbs(jnp.zeros_like, bc), bc)
        out = mp.add(out, bc)
    return out


# pure pytree arithmetic — jittable without the plan key, so the sharded
# path (whose shard_map compiles outside the plan-keyed wrappers because
# plan equality/hash excludes the mesh) still gets a compiled epilogue
# instead of hundreds of eager per-limb ops per call
_apply_epilogue_jit = jax.jit(_apply_epilogue)


# --------------------------------------------------------------------------
# sharded execution: SUMMA-style 2-D distribution, all-gather-free output
# --------------------------------------------------------------------------


def _summa_geometry(plan: GemmPlan, k: int):
    """(pr, pc, lcm, kp): mesh extents and the effective SUMMA panel depth.

    One definition for runner and K-streamer: the host-side out-of-core
    loop must slice its chunks on the very panel grid the runner walks,
    or streamed and unstreamed execution would fold different panel
    products (bit-exactness would be lost).
    """
    mesh, ax_m, ax_n = plan.mesh, plan.shard_axis, plan.shard_axis_n
    pr = mesh.shape[ax_m] if ax_m is not None else 1
    pc = mesh.shape[ax_n] if ax_n is not None else 1
    lcm = math.lcm(pr, pc)
    # panel depth never exceeds a device's K chunk, so a small-K problem
    # does not pad its K dimension up to a full (oversized) panel
    kp = max(1, min(plan.k_panel or plan.bk, -(-k // lcm)))
    return pr, pc, lcm, kp


def _summa_runner(plan: GemmPlan, m: int, k: int, n: int, nl: int):
    """Build the ``shard_map``-wrapped SUMMA loop for one global shape.

    Layout (the classic SUMMA block distribution, DESIGN.md §11):

      * A's rows shard over ``shard_axis`` (Pr), its K columns over
        ``shard_axis_n`` (Pc);
      * B's K rows shard over ``shard_axis`` (Pr), its columns over
        ``shard_axis_n`` (Pc);
      * C' blocks live at ``P(shard_axis, shard_axis_n)`` and never move.

    Each of the ``Kpad / k_panel`` K-steps replicates the owning column's
    A row-panel along ``shard_axis_n`` and the owning row's B column-panel
    along ``shard_axis``, then folds the local ``(m_loc, kp) @ (kp, n_loc)``
    panel product into the loop-carried accumulator with a tier add.  This
    is the engine's analogue of the paper's DDR→BRAM panel streaming: the
    carry is the BRAM-resident C' tile, the per-step panels are the
    streamed operands.  Two panel-movement schedules (``plan.comm``):

      * ``"ring"`` (default) — a ``lax.ppermute`` ring: the owner injects
        its panel and it travels hop-by-hop around the axis (keep-selects
        at each hop), pure data movement with no reduction arithmetic, and
        the loop carry **double-buffers** the in-flight panel — the hops
        for step ``t+1`` are issued before step ``t``'s dot retires, so
        communication overlaps compute.  The loop is seeded by
        pre-rotating panel 0 into the buffers (Cannon-style starting
        alignment).
      * ``"psum"`` — the legacy masked all-reduce (non-owners contribute
        exact zero limbs), kept selectable as the conformance reference:
        both schedules deliver bit-identical panels and fold them in the
        same global K order, so ring output is bit-identical to psum.

    Returns ``(run, (mpad, npad, kpad))`` where
    ``run(*a_limbs, *b_limbs, *acc_limbs)`` maps padded 2-D operands plus
    an initial (padded, block-sharded) accumulator to the padded,
    still-2-D-sharded ``acc + A @ B``.  Threading the accumulator through
    as an operand is what lets the out-of-core K-streamer continue the
    *same* left-to-right panel fold across host-sliced chunks.
    """
    from jax.sharding import PartitionSpec as P

    mesh, ax_m, ax_n = plan.mesh, plan.shard_axis, plan.shard_axis_n
    pr, pc, lcm, kp = _summa_geometry(plan, k)
    # K pads so every device's contiguous chunk is whole panels: A splits K
    # over the column axis, B over the row axis, so both chunkings must be
    # panel-aligned (zero padding is exact in multi-limb arithmetic)
    kpad = _round_up(k, kp * lcm)
    mpad, npad = _round_up(m, pr), _round_up(n, pc)
    ka, kb = kpad // pc, kpad // pr  # local K chunk held of A / of B
    steps = kpad // kp

    def local(*limbs):
        al = mp.from_limbs(limbs[:nl])           # (mpad/pr, ka)
        bl = mp.from_limbs(limbs[nl:2 * nl])     # (kb, npad/pc)
        acc0 = mp.from_limbs(limbs[2 * nl:])     # (mpad/pr, npad/pc)
        m_loc, n_loc = al.shape[0], bl.shape[1]
        ci = jax.lax.axis_index(ax_n) if ax_n is not None else None
        ri = jax.lax.axis_index(ax_m) if ax_m is not None else None

        def bcast_psum(panel, owner, me, axis_name):
            """Replicate the owner's panel along ``axis_name`` as a masked
            all-reduce (exact: non-owners contribute zero limbs)."""
            if axis_name is None:
                return panel
            return mp.map_limbs(
                lambda l: jax.lax.psum(
                    jnp.where(me == owner, l, jnp.zeros_like(l)),
                    axis_name), panel)

        def bcast_ring(panel, owner, me, axis_name, size):
            """Replicate the owner's panel along ``axis_name`` by walking
            it around a ``ppermute`` ring: at hop ``s`` the device at ring
            distance ``s`` downstream of the owner latches the in-flight
            panel and keeps forwarding it.  Pure data movement + selects —
            no reduction arithmetic — and each hop is one neighbor edge,
            so per-link traffic is one panel per step regardless of the
            axis size (vs the all-reduce's 2(size-1) panel transits)."""
            if axis_name is None or size == 1:
                return panel
            dist = (me - owner) % size
            perm = [(s, (s + 1) % size) for s in range(size)]
            # limbs coalesced into ONE buffer so each hop is a single wire
            # message (stack/unstack moves no bits, so conformance with
            # the per-limb psum path is unaffected); non-owners start with
            # their own (wrong) local slice, but a device at distance s
            # latches the in-flight value exactly at hop s — forwarded
            # from distance s-1, which latched the true panel one hop
            # earlier — so stale slices never propagate
            held = jnp.stack(tuple(mp.limbs(panel)))
            for s in range(1, size):
                fwd = jax.lax.ppermute(held, axis_name, perm)
                held = jnp.where(dist == s, fwd, held)
            return mp.from_limbs(tuple(held[i] for i in range(nl)))

        def fetch(t):
            """Slice + replicate the step-``t`` panels (both schedules
            deliver bit-identical panels; only the wire pattern differs)."""
            g = t * kp                          # global K offset of panel t
            own_a, off_a = g // ka, g % ka      # column owning A(:, panel t)
            own_b, off_b = g // kb, g % kb      # row owning B(panel t, :)
            apan = mp.map_limbs(
                lambda l: jax.lax.dynamic_slice(l, (0, off_a), (m_loc, kp)),
                al)
            bpan = mp.map_limbs(
                lambda l: jax.lax.dynamic_slice(l, (off_b, 0), (kp, n_loc)),
                bl)
            if plan.comm == "ring":
                apan = bcast_ring(apan, own_a, ci, ax_n, pc)
                bpan = bcast_ring(bpan, own_b, ri, ax_m, pr)
            else:
                apan = bcast_psum(apan, own_a, ci, ax_n)
                bpan = bcast_psum(bpan, own_b, ri, ax_m)
            return apan, bpan

        def hooks(apan, bpan, t):
            # chaos hooks: a "summa.panel.*" injection zeroes the chosen
            # K-step's panel AS USED (a lost broadcast / dropped ring
            # hop); inert identity without an armed FaultPlan, and
            # inject() drops the _summa_runner_jit cache so faulty traces
            # stay in scope
            return (_faults.zero_panel("summa.panel.a", apan, t),
                    _faults.zero_panel("summa.panel.b", bpan, t))

        if plan.comm == "ring":
            def step(t, carry):
                acc_l, ap_l, bp_l = carry
                # issue the NEXT panel's ring hops before this step's dot:
                # the in-flight ppermute overlaps the compute (the double
                # buffer is the loop carry)
                nxt_a, nxt_b = fetch(t + 1)
                apan, bpan = hooks(mp.from_limbs(ap_l),
                                   mp.from_limbs(bp_l), t)
                acc = mp.add(mp.from_limbs(acc_l),
                             _execute_2d(plan, apan, bpan))
                return (tuple(mp.limbs(acc)), tuple(mp.limbs(nxt_a)),
                        tuple(mp.limbs(nxt_b)))

            a0, b0 = fetch(jnp.asarray(0))  # pre-rotate to start alignment
            acc_l, ap_l, bp_l = jax.lax.fori_loop(
                0, steps - 1, step,
                (tuple(mp.limbs(acc0)), tuple(mp.limbs(a0)),
                 tuple(mp.limbs(b0))))
            # last step peeled: nothing left to prefetch, so the whole
            # schedule issues exactly `steps` panel broadcasts (same wire
            # traffic count as the psum schedule, minus the replication)
            apan, bpan = hooks(mp.from_limbs(ap_l), mp.from_limbs(bp_l),
                               steps - 1)
            acc = mp.add(mp.from_limbs(acc_l), _execute_2d(plan, apan, bpan))
            return tuple(mp.limbs(acc))

        def step(t, carry):
            apan, bpan = hooks(*fetch(t), t)
            acc = mp.add(mp.from_limbs(carry),
                         _execute_2d(plan, apan, bpan))
            return tuple(mp.limbs(acc))

        return jax.lax.fori_loop(0, steps, step, tuple(mp.limbs(acc0)))

    blk = P(ax_m, ax_n)
    run = jax.shard_map(
        local, mesh=mesh,
        in_specs=(blk,) * (3 * nl),
        # the output stays 2-D block-sharded: each device drains its own C'
        # block, no all-gather — consumers slice or keep computing
        # shard-local (the paper's independent per-PE Feed/Drain)
        out_specs=(blk,) * nl,
        check_vma=False,
    )
    return run, (mpad, npad, kpad)


# compile-once cache for the SUMMA runner: shard_map applied eagerly
# re-traces its body every call (thousands of ops per limb at the qd tier —
# the cost the plan-keyed jit wrappers above exist to avoid), so the built
# runner is jitted and memoized.  The mesh must be part of the key
# explicitly: plan equality/hash EXCLUDES the mesh field, so two plans that
# compare equal can still target different meshes.
@functools.lru_cache(maxsize=128)
def _summa_runner_jit(plan: GemmPlan, mesh, m: int, k: int, n: int,
                      nl: int):
    assert mesh is plan.mesh or mesh == plan.mesh
    run, pads = _summa_runner(plan, m, k, n, nl)
    return jax.jit(run), pads


def _execute_sharded(plan: GemmPlan, a, b):
    nl = mp.nlimbs(a)
    m, k = a.shape[-2:]
    n = b.shape[-1]
    if plan.k_stream is not None and k > plan.k_stream:
        return _execute_k_stream(plan, a, b)
    run, (mpad, npad, kpad) = _summa_runner_jit(plan, plan.mesh, m, k, n,
                                                nl)

    def run2d(x, y):
        z = mp.zeros((mpad, npad), plan.precision,
                     dtype=mp.limbs(x)[0].dtype)
        out = run(*mp.limbs(_pad(x, mpad, kpad)),
                  *mp.limbs(_pad(y, kpad, npad)), *mp.limbs(z))
        if (mpad, npad) == (m, n):
            return mp.from_limbs(out)  # keeps the 2-D sharded layout
        return mp.from_limbs([l[:m, :n] for l in out])

    if len(a.shape) > 2 or len(b.shape) > 2:
        # batched + sharded: vmap composes OUTSIDE the shard_map — each
        # batch element runs the same SUMMA loop on the same mesh
        return _execute_batched(plan, a, b, inner=run2d)
    return run2d(a, b)


def _execute_k_stream(plan: GemmPlan, a, b):
    """Host-side out-of-core K streaming through the sharded SUMMA runner.

    The host slices A's columns / B's rows into ``k_stream``-deep chunks
    and feeds each through the runner, threading the block-sharded C'
    accumulator from chunk to chunk as the runner's carry operand — the
    software analogue of the paper's DDR-resident operand stream: only one
    chunk's worth of A/B panels is in flight at a time, while C' stays
    device-resident across the whole K walk.

    Bit-exactness vs the unstreamed run is by construction:

      * the chunk width rounds up to a multiple of the panel depth (and to
        at least one whole panel round, ``kp * lcm(pr, pc)``), so streamed
        panels slice at exactly the unstreamed run's global K offsets;
      * the per-chunk plan pins ``k_panel`` to the global run's effective
        panel depth, so a short tail chunk cannot re-derive a smaller one;
      * the tail chunk zero-pads host-side up to the common chunk width —
        zero panels fold as exact no-ops in tier arithmetic (and every
        chunk reuses the single compiled runner);
      * the carry threads through the runner, so the accumulator performs
        the SAME left-to-right panel fold as one unstreamed call.
    """
    nl = mp.nlimbs(a)
    m, k = a.shape[-2:]
    n = b.shape[-1]
    _, _, lcm, kp = _summa_geometry(plan, k)
    ks = max(_round_up(plan.k_stream, kp), kp * lcm)
    sub = plan.with_(k_stream=None, k_panel=kp)
    run, (mpad, npad, kpad) = _summa_runner_jit(sub, sub.mesh, m, ks, n,
                                                nl)

    def run2d(x, y):
        carry = mp.zeros((mpad, npad), plan.precision,
                         dtype=mp.limbs(x)[0].dtype)
        for s in range(0, k, ks):
            xc = mp.map_limbs(lambda l: l[:, s:s + ks], x)
            yc = mp.map_limbs(lambda l: l[s:s + ks, :], y)
            carry = mp.from_limbs(run(
                *mp.limbs(_pad(xc, mpad, kpad)),
                *mp.limbs(_pad(yc, kpad, npad)),
                *mp.limbs(carry)))
        if (mpad, npad) == (m, n):
            return carry
        return mp.from_limbs([l[:m, :n] for l in mp.limbs(carry)])

    if len(a.shape) > 2 or len(b.shape) > 2:
        return _execute_batched(plan, a, b, inner=run2d)
    return run2d(a, b)


# --------------------------------------------------------------------------
# dispatch + failover
# --------------------------------------------------------------------------


def _dispatch_once(plan: GemmPlan, a, b, alpha, beta, c, batched: bool,
                   sharded: bool, check: str):
    """Route one (validated) workload to its path; return (out, flags)."""
    if batched and not sharded:
        return _execute_batched_jit(a, b, alpha, beta, c, plan=plan,
                                    check=check)
    if sharded:
        # _execute_sharded routes batched operands through vmap-outside-
        # shard_map itself, so batched + sharded is one engine call
        out = _execute_sharded(plan, a, b)
        if alpha is not None or c is not None:
            out = _apply_epilogue_jit(out, alpha, beta, c)
        flags = None
        if check != "none":
            # the SUMMA runner compiles outside the plan-keyed wrappers
            # (plan hash excludes the mesh), so guarding it costs one
            # extra eager probe dispatch — accepted: multi-device calls
            # are large enough to amortize it
            flags = guard.probe(a, b, c, out, alpha, beta, plan=plan,
                                check=check)
        return out, flags
    if alpha is not None and plan.backend == "ozaki-pallas":
        # fused drain: the epilogue runs in VMEM before the C' tile drains
        if c is None:
            return _execute_fused_alpha_jit(a, b, alpha, plan=plan,
                                            check=check)
        return _execute_fused_full_jit(a, b, alpha, beta, c, plan=plan,
                                       check=check)
    return _execute_2d_jit(a, b, alpha, beta, c, plan=plan, check=check)


def _fallback_plan(plan: GemmPlan, backend: str, m: int, k: int,
                   n: int) -> GemmPlan:
    """Re-plan the same workload onto a fallback backend.

    Structural parameters (tier, platform, mesh, batch shape, check) carry
    over; backend-specific ones (blocks, slice params) re-solve for the
    new backend.  ``use_cache=False``: the failover path must not consult
    the quarantine it is itself writing, and a tuned-tile lookup is not
    worth a second cache read on an error path.
    """
    return make_plan(
        m, k, n, dtype=plan.limb_dtype, precision=plan.precision,
        backend=backend, batch_shape=plan.batch_shape,
        interpret=plan.interpret, platform=plan.platform, mesh=plan.mesh,
        shard_axis=plan.shard_axis, shard_axis_n=plan.shard_axis_n,
        k_panel=plan.k_panel, comm=plan.comm, k_stream=plan.k_stream,
        check=plan.check, use_cache=False)


def _dispatch_with_failover(plan: GemmPlan, a, b, alpha, beta, c,
                            batched: bool, sharded: bool, check: str):
    """Dispatch, retrying down the plan's fallback chain on backend failure.

    Returns ``(out, flags, used_plan)``.  Failure semantics:

      * backends with an EMPTY chain (xla, ref, unknown) dispatch bare —
        their exceptions re-raise unchanged (failover must not reword the
        engine's own diagnostics, and 'xla' failing means the problem is
        not the backend);
      * :class:`NumericalHazardError` always re-raises — it is a verdict
        about the *data*, and a fallback backend would reach the same one;
      * any other exception quarantines the failing backend (so repeat
        calls skip it at plan time), warns, and retries the next rung;
      * all rungs failing raises :class:`BackendExecutionError` carrying
        every ``(backend, error)`` attempt.

    ``REPRO_GEMM_FAILOVER=0`` disables the whole mechanism (bisection
    wants the original traceback).
    """
    chain = fallback_chain(plan.backend, plan.precision)
    if not chain or os.environ.get(_ENV_FAILOVER, "1") == "0":
        out, flags = _dispatch_once(plan, a, b, alpha, beta, c, batched,
                                    sharded, check)
        return out, flags, plan
    m, k = a.shape[-2:]
    n = b.shape[-1]
    attempts = []
    cur = plan
    for nxt in chain + (None,):
        try:
            out, flags = _dispatch_once(cur, a, b, alpha, beta, c,
                                        batched, sharded, check)
            return out, flags, cur
        except NumericalHazardError:
            raise
        except Exception as e:  # noqa: BLE001 — failover IS the handler
            attempts.append((cur.backend, repr(e)))
            plan_cache.quarantine(cur.platform, cur.backend, cur.nlimbs,
                                  reason=repr(e))
            if nxt is None:
                break
            warnings.warn(
                f"GEMM backend {cur.backend!r} failed "
                f"({type(e).__name__}: {e}); quarantined for "
                f"{plan_cache.QUARANTINE_TTL:.0f}s, failing over to "
                f"{nxt!r}", BackendFailoverWarning, stacklevel=3)
            cur = _fallback_plan(plan, nxt, m, k, n)
    raise BackendExecutionError(
        f"every backend in the fallback chain failed for this "
        f"{plan.precision} GEMM: "
        + "; ".join(f"{be}: {err}" for be, err in attempts)
        + " (REPRO_GEMM_FAILOVER=0 re-raises the first failure directly)",
        attempts=tuple(attempts))


# --------------------------------------------------------------------------
# public entry points
# --------------------------------------------------------------------------


def execute(plan: GemmPlan, a, b, *, alpha=None, beta=None, c=None,
            check: Optional[str] = None, k_stream: Optional[int] = None):
    """Run C = alpha * (A @ B) + beta * C under a plan.

    A: (..., m, k), B: (..., k, n).  ``alpha``/``beta`` (python floats or
    tier scalars) and ``c`` are the optional Rgemm epilogue: fused into the
    kernel drain on the 2-D ``ozaki-pallas`` path, applied as an identical
    tier-arithmetic post-step everywhere else.  With no epilogue operands
    this is plain C = A @ B; with ``c`` alone, alpha and beta default to
    1.0 (C is *added*, never silently dropped).  BLAS semantics govern
    beta: ``beta == 0`` means C is **not read** (NaN/Inf in C cannot
    leak), and a nonzero beta without ``c=`` raises rather than being
    silently dropped.

    ``check`` selects the guarded-execution level (defaults to the plan's
    ``check`` field): ``"none"`` propagates hazards IEEE-style, zero
    overhead; ``"finite"`` raises a typed
    :class:`~repro.runtime.faults.NumericalHazardError` /
    :class:`~repro.runtime.faults.SliceOverflowError` naming the offending
    operand on NaN/Inf input-or-output or sliced-backend operand overflow;
    ``"full"`` additionally validates the result against an f64 shadow
    product (catches finite-but-wrong results — flipped limbs, lost SUMMA
    panels).  Guarded raising degrades to propagation under an outer jit
    (flags are tracers there); see ``gemm.guard``.

    ``k_stream`` (per-call override of the plan field) turns on host-side
    out-of-core K streaming on sharded plans: A/B feed through the SUMMA
    runner in ``k_stream``-deep K chunks while the block-sharded C'
    accumulator stays device-resident, and the result is bit-identical to
    the unstreamed call (see ``_execute_k_stream``).

    Backend compile/run failures retry down the plan's declared fallback
    chain (``ozaki-pallas → ozaki → xla``), quarantining each failed
    backend in the plan cache; exhaustion raises
    :class:`~repro.runtime.faults.BackendExecutionError`.
    """
    check = guard.resolve_check(check, plan)
    if k_stream is not None:
        if plan.mesh is None:
            raise ValueError(
                "k_stream= requires a sharded plan (mesh=): the out-of-"
                "core K stream feeds chunks through the SUMMA runner")
        if k_stream <= 0:
            raise ValueError(f"k_stream must be positive, got {k_stream}")
        plan = plan.with_(k_stream=k_stream)
    prec = mp.precision_of(a)
    if mp.precision_of(b) != prec:
        raise TypeError(f"operand tiers differ: {mp.precision_of(a)} vs "
                        f"{mp.precision_of(b)}")
    if prec != plan.precision:
        raise ValueError(
            f"plan is for precision={plan.precision!r} but operands are "
            f"{prec!r}; rebuild with make_plan(..., precision={prec!r}) "
            f"(engine.matmul infers this from the operand type)")
    if a.shape[-1] != b.shape[-2]:
        raise ValueError(f"inner dims mismatch: {a.shape} x {b.shape}")
    limb_dtype = mp.limbs(a)[0].dtype
    if beta is not None and c is None:
        # BLAS Rgemm: beta scales C, so beta without a C operand is
        # meaningful only when it is zero ("C is not read").  Anything
        # else would be silently dropped — raise instead, mirroring the
        # alpha/c defaulting rules (c alone => alpha = beta = 1, never a
        # dropped operand)
        if not _static_zero(beta):
            raise ValueError(
                f"beta={beta!r} was passed without c=; beta scales C, so "
                f"a nonzero (or traced) beta without a C operand would be "
                f"silently dropped — pass c=, or beta=0 (BLAS: C not read)")
        beta = None
    if c is not None and _static_zero(beta):
        # BLAS: beta == 0 means C is NOT read — drop the term outright so
        # a NaN/Inf in C cannot leak through 0 * C (traced zero betas get
        # the same guarantee from the where-guard in _apply_epilogue /
        # the fused kernel drain)
        c = beta = None
    if c is not None and alpha is None:
        alpha = 1.0
    if alpha is not None:
        alpha = _as_scalar(alpha, prec, limb_dtype)
    if c is not None:
        beta = _as_scalar(1.0 if beta is None else beta, prec, limb_dtype)
        if mp.precision_of(c) != prec:
            raise TypeError(f"C tier {mp.precision_of(c)} != operand "
                            f"tier {prec}")
    batched = len(a.shape) > 2 or len(b.shape) > 2
    # either axis suffices: a 1-axis mesh claimed entirely by an explicit
    # shard_axis_n= is pure column sharding (shard_axis stays None), which
    # the SUMMA loop handles — it must not silently run unsharded
    sharded = plan.mesh is not None and (
        plan.shard_axis is not None or plan.shard_axis_n is not None)
    if batched and plan.batch == "none":
        raise ValueError(
            "plan was made for 2-D operands but inputs have batch dims; "
            "rebuild with batch_shape= (engine.matmul does this)")
    if _faults.active():
        # chaos hooks run EAGERLY, outside the plan-keyed jit wrappers —
        # corrupting inside a traced body would cache the corrupted graph
        # under the plan key and leak the fault past its FaultPlan
        a = _faults.corrupt("gemm.a", a)
        b = _faults.corrupt("gemm.b", b)
        if c is not None:
            c = _faults.corrupt("gemm.c", c)
    out, flags, used = _dispatch_with_failover(
        plan, a, b, alpha, beta, c, batched, sharded, check)
    if _faults.active():
        out2 = _faults.corrupt("gemm.out", out)
        if out2 is not out:
            # the in-graph flags saw the clean product; re-probe the
            # corrupted one eagerly so the guard judges what the caller
            # will actually receive
            out = out2
            if check != "none":
                flags = guard.probe(a, b, c, out, alpha, beta, plan=used,
                                    check=check)
    shapes = {"A": tuple(a.shape), "B": tuple(b.shape),
              "output": tuple(out.shape)}
    if c is not None:
        shapes["C"] = tuple(c.shape)
    guard.raise_on_flags(flags, used, check, shapes)
    return out


def matmul(a, b, *, plan: Optional[GemmPlan] = None, alpha=None, beta=None,
           c=None, **overrides):
    """Plan-and-execute convenience: the repo-wide GEMM entry point.

    The precision tier is inferred from the operand type (``dd.DD`` ->
    ``"dd"``, ``td.TD`` -> ``"td"``, ``qd.QD`` -> ``"qd"``) unless
    overridden.  ``overrides`` are
    forwarded to ``make_plan`` (backend=, bm/bn/bk=, mesh=, shard_axis=,
    ...); pass a prebuilt ``plan`` to skip planning.  The two are exclusive
    — a plan already fixes every decision, so overrides alongside it would
    be silently dead.  ``alpha``/``beta``/``c`` are the optional Rgemm
    epilogue operands (see ``execute``); ``core.blas.rgemm`` routes its
    epilogue through here so fusion-capable backends can claim it.
    """
    if plan is not None and overrides:
        raise ValueError(
            f"pass either plan= or planner overrides, not both "
            f"(got overrides {sorted(overrides)} with an explicit plan; "
            f"use plan.with_(...) to modify it)")
    if plan is None:
        m, k = a.shape[-2:]
        k2, n = b.shape[-2:]
        if k != k2:
            raise ValueError(f"inner dims mismatch: {a.shape} x {b.shape}")
        batch_shape = jnp.broadcast_shapes(a.shape[:-2], b.shape[:-2])
        overrides.setdefault("precision", mp.precision_of(a))
        plan = make_plan(m, k, n, dtype=a.limbs()[0].dtype,
                         batch_shape=batch_shape, **overrides)
    return execute(plan, a, b, alpha=alpha, beta=beta, c=c)
