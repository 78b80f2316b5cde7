"""Quickstart: binary128-class GEMM in three backends + the accuracy story.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np
import jax.numpy as jnp

from repro.core import dd
from repro.core.blas import rgemm
from repro.core.gemm import matmul
from repro.runtime import compile_cache
from repro.solve import rgesv


def main():
    compile_cache.enable()
    rng = np.random.default_rng(0)
    n = 96
    a = dd.from_float(jnp.asarray(rng.random((n, n))))
    b = dd.from_float(jnp.asarray(rng.random((n, n))))

    print("== C = A @ B in binary128-class (double-word) arithmetic ==")
    c_ozaki = matmul(a, b, backend="ozaki")    # error-free slices on native GEMM
    c_pallas = matmul(a, b, backend="pallas")  # the paper's systolic design
    c_xla = matmul(a, b, backend="xla")        # per-element DD fallback

    for name, c in (("ozaki", c_ozaki), ("pallas", c_pallas), ("xla", c_xla)):
        d = np.abs((np.asarray(c.hi) - np.asarray(c_ozaki.hi))
                   + (np.asarray(c.lo) - np.asarray(c_ozaki.lo))).max()
        print(f"  {name:7s} max |diff vs ozaki| = {d:.3e}")

    print("\n== the precision gap the paper closes ==")
    an, bn = np.asarray(dd.to_float(a)), np.asarray(dd.to_float(b))
    e_f64 = np.abs(an @ bn - (np.asarray(c_ozaki.hi) + np.asarray(c_ozaki.lo))).mean()
    print(f"  E_L1(double vs binary128-class) = {e_f64:.3e}  "
          "(paper: double is 100-1000x slower to fix on CPU)")

    print("\n== Rgemm API (paper Listing 1): C = alpha*op(A)@op(B) + beta*C ==")
    c0 = dd.from_float(jnp.asarray(rng.random((n, n))))
    out = rgemm("n", "t", 2.0, a, b, -1.0, c0)
    ref = 2.0 * (an @ bn.T) - np.asarray(dd.to_float(c0))
    print(f"  max |rgemm - numpy f64 ref| = "
          f"{np.abs(np.asarray(dd.to_float(out)) - ref).max():.3e} "
          "(f64-level agreement; dd carries ~1e-32 internally)")

    print("\n== tiered refinement solve (repro.solve, DESIGN.md §10) ==")
    a_np = np.asarray(rng.random((n, n))) + n * np.eye(n)
    b_np = a_np @ rng.standard_normal((n, 2))
    # factor once at plain f64, refine residuals at the dd tier through
    # the engine (r = b - A x is ONE fused-epilogue GEMM per iteration)
    x, info = rgesv(a_np, b_np, factor_tier="f64", target_tier="dd")
    print(f"  rgesv f64-factor -> dd-refine: converged={info.converged} "
          f"in {info.iterations} iterations")
    print("  backward errors per iteration:",
          " ".join(f"{e:.1e}" for e in info.backward_errors))


if __name__ == "__main__":
    main()
