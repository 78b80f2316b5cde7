"""Public jit'd entry points for the extended-precision GEMM kernels.

``ddgemm`` is now a thin shim over the unified execution engine
(``repro.gemm``), which owns the zero-padding to block multiples (zeros are
exact in multi-limb arithmetic, so padding never changes the result),
block-shape clamping, and tuned-tile lookup that used to live here.
``interpret=None`` auto-selects interpret mode off-TPU so the same call
site deploys unchanged on hardware.  ``matmul_ml_xla`` is the blocked-XLA
backend implementation the engine dispatches to — count-generic over
``core.mp``, with ``matmul_dd_xla``/``matmul_qd_xla`` kept as named tier
bindings.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import dd, mp, qd
from repro.gemm.plan import round_up as _round_up
from .ddgemm import DEFAULT_BLOCKS  # noqa: F401  (re-export for tuners)

__all__ = ["ddgemm", "matmul_ml_xla", "matmul_dd_xla", "matmul_qd_xla"]


def _pad_to(x, rows, cols):
    r, c = x.shape
    if r == rows and c == cols:
        return x
    return jnp.pad(x, ((0, rows - r), (0, cols - c)))


def ddgemm(a: dd.DD, b: dd.DD, *, bm: int | None = None, bn: int | None = None,
           bk: int | None = None, interpret: bool | None = None) -> dd.DD:
    """C = A @ B in double-word arithmetic via the Pallas systolic-tile kernel."""
    from repro import gemm as engine

    return engine.matmul(a, b, backend="pallas", bm=bm, bn=bn, bk=bk,
                         interpret=interpret)


def matmul_ml_xla(a, b, *, chunk: int = 16):
    """Blocked XLA (non-Pallas) multi-limb matmul — the 'host fallback'.

    Streams K in chunks; each chunk materializes exact (m, chunk, n) tier
    products and reduces them with the compensated halving tree, at
    whatever limb count the operands carry.  Used for CPU-side
    benchmarking at sizes where the O(m*k*n) oracle is infeasible.
    """
    m, k = a.shape
    _, n = b.shape
    kp = _round_up(k, chunk)
    # opaque runtime operands (the engine._pad pin): under an outer jit
    # with constant operands XLA would otherwise constant-fold the whole
    # EFT chain without the fma contraction the compiled path applies to
    # dd.mul's cross terms, and the two spellings would differ in the
    # last bits
    al, bl = jax.lax.optimization_barrier((
        tuple(_pad_to(l, m, kp) for l in mp.limbs(a)),
        tuple(_pad_to(l, kp, n) for l in mp.limbs(b))))
    a, b = mp.from_limbs(al), mp.from_limbs(bl)
    nchunks = kp // chunk

    def body(acc, idx):
        a_blk = mp.from_limbs([
            jax.lax.dynamic_slice_in_dim(l, idx * chunk, chunk, 1)
            for l in mp.limbs(a)])
        b_blk = mp.from_limbs([
            jax.lax.dynamic_slice_in_dim(l, idx * chunk, chunk, 0)
            for l in mp.limbs(b)])
        prods = mp.mul(
            mp.map_limbs(lambda l: l[:, :, None], a_blk),
            mp.map_limbs(lambda l: l[None, :, :], b_blk),
        )
        part = mp.sum_(prods, axis=1)
        return mp.add(acc, part), None

    init = mp.zeros((m, n), mp.precision_of(a), dtype=mp.limbs(a)[0].dtype)
    acc, _ = jax.lax.scan(body, init, jnp.arange(nchunks))
    return acc


def matmul_dd_xla(a: dd.DD, b: dd.DD, *, chunk: int = 16) -> dd.DD:
    """Blocked XLA DD matmul — the 2-limb binding of ``matmul_ml_xla``."""
    return matmul_ml_xla(a, b, chunk=chunk)


def matmul_qd_xla(a: qd.QD, b: qd.QD, *, chunk: int = 16) -> qd.QD:
    """Blocked XLA QD matmul — the 4-limb binding of ``matmul_ml_xla``."""
    return matmul_ml_xla(a, b, chunk=chunk)
