"""Pallas fused Ozaki-slice GEMM kernel — MXU-resident slicing + recombination.

The third kernel family, and the one that actually maps the paper's wide
multiplier onto the TPU's matrix unit (DESIGN.md §9).  Where
``kernels/ddgemm.py`` / ``kernels/qdgemm.py`` spend the K loop on ``bk``
scalar rank-1 EFT waves on the VPU, each grid cell here:

  1. **slices** its (bm, bk) A-slab and (bk, bn) B-slab into error-free
     Rump splits on a per-row/col power-of-two grid ladder
     (``core.ozaki._extract_slices`` — the same extraction the XLA Ozaki
     backend uses, running on VMEM-resident tiles);
  2. runs the triangular set of slice-pair products as block ``jnp.dot``s
     in the accumulator dtype — bf16 x bf16 -> f32 on the MXU on TPU, f64
     on CPU/interpret — summing each diagonal (equal s + t) natively,
     exact by the ``slice_params`` headroom;
  3. **recombines diagonals into the multi-limb (dd/td/qd) accumulator
     inside VMEM scratch**, one fold per diagonal, so recombination
     traffic never round-trips HBM;
  4. at the drain step optionally applies the Rgemm **alpha/beta epilogue**
     in tier arithmetic before the C' tile leaves VMEM (``epilogue=``:
     ``"none"`` | ``"alpha"`` | ``"full"``).

Because slices are taken per K-slab (depth ``bk``, not the full K), the
exactness condition 2*beta + log2(bk * n_slices) <= p_acc leaves far more
bits per slice than whole-K slicing — the plan layer solves (beta,
n_slices) for the slab depth and threads them here as static parameters.

Validated in interpret mode by the cross-backend conformance matrix
(tests/test_conformance.py) at every tier and by tests/test_ozgemm_kernel.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import mp
from repro.core.ozaki import slab_update

__all__ = ["ozgemm_kernel_call", "EPILOGUES"]

EPILOGUES = ("none", "alpha", "full")


def _ozgemm_kernel(*refs, nlimbs: int, beta: int, n_slices: int,
                   slice_dtype: str, acc_dtype: str, epilogue: str,
                   full: bool):
    # refs layout: nlimbs A + nlimbs B [+ nlimbs alpha (1,1)]
    #   [+ nlimbs beta (1,1) + nlimbs C] inputs, then nlimbs outputs, then
    #   nlimbs VMEM accumulator scratch
    a_refs = refs[:nlimbs]
    b_refs = refs[nlimbs:2 * nlimbs]
    pos = 2 * nlimbs
    alpha_refs = beta_refs = c_refs = ()
    if epilogue != "none":
        alpha_refs = refs[pos:pos + nlimbs]
        pos += nlimbs
    if epilogue == "full":
        beta_refs = refs[pos:pos + nlimbs]
        c_refs = refs[pos + nlimbs:pos + 2 * nlimbs]
        pos += 2 * nlimbs
    o_refs = refs[pos:pos + nlimbs]
    acc_refs = refs[pos + nlimbs:]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        for r in acc_refs:
            r[...] = jnp.zeros_like(r)

    a = mp.from_limbs([r[...] for r in a_refs])  # (bm, bk)
    b = mp.from_limbs([r[...] for r in b_refs])  # (bk, bn)
    acc = slab_update(
        mp.from_limbs([r[...] for r in acc_refs]), a, b,
        beta=beta, n_slices=n_slices,
        slice_dtype=slice_dtype, acc_dtype=acc_dtype, full=full)
    for r, v in zip(acc_refs, acc.limbs()):
        r[...] = v

    @pl.when(k == pl.num_programs(2) - 1)
    def _drain():
        res = mp.from_limbs([r[...] for r in acc_refs])
        if epilogue != "none":
            alpha = mp.from_limbs([r[...] for r in alpha_refs])  # (1, 1)
            res = mp.mul(mp.broadcast_to(alpha, res.shape), res)
        if epilogue == "full":
            beta_s = mp.from_limbs([r[...] for r in beta_refs])
            c = mp.from_limbs([r[...] for r in c_refs])
            bc = mp.mul(mp.broadcast_to(beta_s, c.shape), c)
            # BLAS: beta == 0 means C is NOT read — statically-zero betas
            # never reach the kernel (the engine drops C), but a beta that
            # is only zero at run time (traced epilogue operand) must not
            # leak NaN/Inf from C through 0 * C; the select discards the
            # poisoned product.  Same guard as engine._apply_epilogue.
            bc = mp.where(jnp.broadcast_to(mp.is_zero(beta_s), bc.shape),
                          mp.map_limbs(jnp.zeros_like, bc), bc)
            res = mp.add(res, bc)
        for o, v in zip(o_refs, res.limbs()):
            o[...] = v


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "beta", "n_slices",
                              "slice_dtype_name", "acc_dtype_name",
                              "epilogue", "full", "interpret")
)
def ozgemm_kernel_call(*operands, bm: int, bn: int, bk: int, beta: int,
                       n_slices: int, slice_dtype_name: str,
                       acc_dtype_name: str, epilogue: str = "none",
                       full: bool = False, interpret: bool = True):
    """Raw kernel invocation (block-multiple shapes only).

    ``operands``: nlimbs A limbs + nlimbs B limbs; with ``epilogue="alpha"``
    also nlimbs (1, 1) alpha limbs; with ``"full"`` additionally nlimbs
    (1, 1) beta limbs and nlimbs (m, n) C limbs.  Use the engine
    (``repro.gemm.execute`` with a ``backend="ozaki-pallas"`` plan) for the
    padded/public entry point.
    """
    if epilogue not in EPILOGUES:
        raise ValueError(f"unknown epilogue {epilogue!r}; one of {EPILOGUES}")
    per_limb = {"none": 2, "alpha": 3, "full": 5}[epilogue]
    nlimbs, rem = divmod(len(operands), per_limb)
    assert rem == 0 and nlimbs in mp.PRECISIONS.values(), (
        len(operands), epilogue)
    a_limbs = operands[:nlimbs]
    m, k = a_limbs[0].shape
    k2, n = operands[nlimbs].shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0, (
        (m, k, n), (bm, bn, bk))
    dtype = a_limbs[0].dtype
    grid = (m // bm, n // bn, k // bk)

    a_spec = pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))
    b_spec = pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))
    # int32 block indices: under x64 a python-int 0 is an i64, which
    # Mosaic cannot return from an index map
    scalar_spec = pl.BlockSpec((1, 1),
                               lambda i, j, kk: (jnp.int32(0),) * 2)
    c_spec = pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))
    in_specs = [a_spec] * nlimbs + [b_spec] * nlimbs
    if epilogue != "none":
        in_specs += [scalar_spec] * nlimbs
    if epilogue == "full":
        in_specs += [scalar_spec] * nlimbs + [c_spec] * nlimbs

    kern = functools.partial(
        _ozgemm_kernel, nlimbs=nlimbs, beta=beta, n_slices=n_slices,
        slice_dtype=slice_dtype_name, acc_dtype=acc_dtype_name,
        epilogue=epilogue, full=full)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[c_spec] * nlimbs,
        out_shape=[jax.ShapeDtypeStruct((m, n), dtype)] * nlimbs,
        scratch_shapes=[pltpu.VMEM((bm, bn), dtype)] * nlimbs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
