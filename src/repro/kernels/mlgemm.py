"""Count-generic Pallas systolic-tile kernel for multi-limb GEMM.

One kernel for every rung of the precision ladder: the limb count is a
parameter, not a code path.  FPGA -> TPU mapping (see DESIGN.md §2):

  * the `P_R x P_C` PE array  ->  the (M/bm, N/bn) Pallas grid: each grid
    cell owns one (bm, bn) output tile and its VMEM accumulator planes,
    exactly as a PE owns one C' element;
  * the systolic pulse (A by column / B by row each cycle)  ->  the
    *sequential* K grid dimension: at step k the cell consumes the
    (bm, bk) slab of A and (bk, bn) slab of B — ``nlimbs`` planes each —
    performs `bk` rank-1 multi-limb multiply-add waves, and keeps the
    running sum in ``nlimbs`` VMEM scratch planes;
  * the `M_Tile` on-chip buffer  ->  the BlockSpec block shapes: Pallas
    stages each block HBM->VMEM, the cache the paper adds in front of the
    Feed module.

The multiply-add inside a wave is the tier's FMA resolved through
``repro.core.mp`` from the plane count — dd's specialized Dekker/Li EFT
chain at 2 planes, the generic exact-product + branch-free-renormalize
recipe at 3 (td) and 4 (qd).  This is the runtime analogue of the
run-time-reconfigurable multi-precision FPGA IP cores: the architecture is
fixed, the digit count is a dispatch-time knob, and per-wave cost scales
with the limb count the plan layer's ``precision`` axis exposes.  The
autotune cache keys on limb count so every tier tunes independently.

``kernels/ddgemm.py`` and ``kernels/qdgemm.py`` remain as thin 2-/4-plane
bindings.  Validated in interpret mode against ``kernels/ref`` by the
cross-backend conformance matrix (tests/test_conformance.py) at every
count.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import mp

__all__ = ["mlgemm_kernel_call"]


def _mlgemm_kernel(*refs, bk: int, nlimbs: int):
    # refs: nlimbs A-limb refs, nlimbs B-limb refs, nlimbs out refs,
    # nlimbs accumulator scratch planes
    a_refs, b_refs = refs[:nlimbs], refs[nlimbs:2 * nlimbs]
    o_refs = refs[2 * nlimbs:3 * nlimbs]
    acc_refs = refs[3 * nlimbs:]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        for r in acc_refs:
            r[...] = jnp.zeros_like(r)

    a = [r[...] for r in a_refs]  # (bm, bk) x nlimbs
    b = [r[...] for r in b_refs]  # (bk, bn) x nlimbs
    lane = jax.lax.broadcasted_iota(jnp.int32, a[0].shape, 1)
    sublane = jax.lax.broadcasted_iota(jnp.int32, b[0].shape, 0)

    def pick(x, hit, axis):
        # exact select of one column/row: every other term is +0.  Mosaic
        # has no dynamic slice of a value, so a masked sum stands in for it
        return jnp.sum(jnp.where(hit, x, jnp.zeros_like(x)), axis=axis,
                       keepdims=True)

    def wave(i, carry):
        # one systolic wave: acc += outer(a_col, b_row) in tier arithmetic;
        # (bm, 1) x (1, bn) broadcasts through the EFT chains to the tile
        a_col = mp.from_limbs([pick(x, lane == i, 1) for x in a])
        b_row = mp.from_limbs([pick(x, sublane == i, 0) for x in b])
        out = mp.fma(mp.from_limbs(list(carry)), a_col, b_row)
        return tuple(mp.limbs(out))

    # int32 bounds: under x64 a python-int loop index is int64, which
    # Mosaic cannot lower
    acc = jax.lax.fori_loop(jnp.int32(0), jnp.int32(bk), wave,
                            tuple(r[...] for r in acc_refs))
    for r, v in zip(acc_refs, acc):
        r[...] = v

    @pl.when(k == pl.num_programs(2) - 1)
    def _store():
        for o, r in zip(o_refs, acc_refs):
            o[...] = r[...]


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def mlgemm_kernel_call(*limbs, bm: int, bn: int, bk: int,
                       interpret: bool = True):
    """Raw kernel invocation on nlimbs A limbs + nlimbs B limbs.

    The limb count is inferred from the argument count (``len(limbs) // 2``)
    and must name a registered tier; shapes must be block multiples.  Use
    the engine (``repro.gemm.execute``) for the padded/public entry point.
    """
    assert len(limbs) % 2 == 0, len(limbs)
    nlimbs = len(limbs) // 2
    mp.precision_for_count(nlimbs)  # raises on an unregistered count
    a_limbs, b_limbs = limbs[:nlimbs], limbs[nlimbs:]
    m, k = a_limbs[0].shape
    k2, n = b_limbs[0].shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0, (
        (m, k, n), (bm, bn, bk))
    dtype = a_limbs[0].dtype
    grid = (m // bm, n // bn, k // bk)
    out_shape = [jax.ShapeDtypeStruct((m, n), dtype)] * nlimbs
    kern = functools.partial(_mlgemm_kernel, bk=bk, nlimbs=nlimbs)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=(
            [pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))] * nlimbs
            + [pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))] * nlimbs
        ),
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))] * nlimbs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), dtype)] * nlimbs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*limbs)
