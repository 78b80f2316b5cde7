"""Blocked LU decomposition in binary128-class arithmetic (paper §V-A).

Factorizes a random [0,1) matrix (the paper's test), solves a linear
system, and shows the residual gap vs double precision.

    PYTHONPATH=src python examples/lu_decomposition.py [n]
"""

import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import dd
from repro.core.linalg import lu_solve, rgetrf
from repro.runtime import compile_cache


def _f64(v):
    """A dd value rounded once to f64 (exact for f32 limbs)."""
    return np.asarray(v.hi, np.float64) + np.asarray(v.lo, np.float64)


def main(n: int = 128):
    compile_cache.enable()
    # the plan layer refuses f64 limbs on the TPU (XLA emulates f64 there)
    limb = jnp.float32 if jax.default_backend() == "tpu" else jnp.float64
    rng = np.random.default_rng(1)
    a_np = rng.random((n, n))
    a = dd.from_float(jnp.asarray(a_np, limb))
    a_np = _f64(a)  # the matrix the limbs hold

    t0 = time.time()
    lu, piv = rgetrf(a, block=32)
    t = time.time() - t0
    gflops = (2 / 3) * n**3 / t / 1e9
    print(f"rgetrf(n={n}, b=32): {t:.2f}s  ({gflops:.4f} binary128-GFlops; "
          f"paper Agilex: 2.5 GFlops at n=20000)")

    lu_np = _f64(lu)
    l = np.tril(lu_np, -1) + np.eye(n)
    u = np.triu(lu_np)
    pa = a_np.copy()
    for j, p in enumerate(piv):
        pa[[j, p]] = pa[[p, j]]
    print(f"max |PA - LU| (f64 view)   = {np.abs(l @ u - pa).max():.3e}")

    # solve A x = b and compare residual against plain f64 LU
    x_true = rng.standard_normal((n, 1))
    b = a_np @ x_true
    x = lu_solve(lu, piv, dd.from_float(jnp.asarray(b, limb)))
    r_dd = np.abs(a_np @ _f64(x) - b).max()
    x64 = np.linalg.solve(a_np, b)
    r_64 = np.abs(a_np @ x64 - b).max()
    print(f"residual |Ax-b|: binary128-class {r_dd:.3e}  vs double {r_64:.3e}")


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 128)
