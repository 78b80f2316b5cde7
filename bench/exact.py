"""Exact host references for the benchmark's correctness check.

Every limb on the chip is an f32, and the product of two f32 values is
exact in f64.  So an entry of A @ B, or a row of b - A x, is an exact sum
of f64 terms, and ``math.fsum`` (correctly rounded) returns the error of
the device's answer, rounded once at the end.  The program under test
contributes only its answers: the operands come from the benchmark's own
generator, and nothing here imports the program.
"""

from __future__ import annotations

import math

import numpy as np


def _f32_limbs(limbs) -> np.ndarray:
    out = np.stack([np.asarray(l) for l in limbs])
    if out.dtype != np.float32:
        raise TypeError(f"exact references need f32 limbs, got {out.dtype}")
    return out.astype(np.float64)


def stratified_entries(rng, m: int, n: int, grid: int):
    """One (row, col) drawn from each cell of a grid x grid partition of an
    m x n output, so that no band of rows or columns goes unchecked."""
    r_edges = np.linspace(0, m, min(grid, m) + 1).astype(int)
    c_edges = np.linspace(0, n, min(grid, n) + 1).astype(int)
    rows, cols = [], []
    for r0, r1 in zip(r_edges[:-1], r_edges[1:]):
        for c0, c1 in zip(c_edges[:-1], c_edges[1:]):
            rows.append(int(rng.integers(r0, r1)))
            cols.append(int(rng.integers(c0, c1)))
    return np.asarray(rows), np.asarray(cols)


def product_errors(a_rows, b_cols, c_vals, block: int = 128) -> np.ndarray:
    """Per sampled entry t: |C_t - exact(A_t . B_t)| / sum_k |a_tk b_tk|.

    ``a_rows``: the limbs of A's sampled rows, each (P, k); ``b_cols``: the
    limbs of B's sampled columns, each (k, P); ``c_vals``: the limbs of the
    device's C at those entries, each (P,).  The denominator is the
    componentwise scale of the dot product, so the figure reads as a
    relative error whatever the cancellation.
    """
    a = _f32_limbs(a_rows)                   # (la, P, k)
    b = _f32_limbs(b_cols).transpose(0, 2, 1)  # (lb, P, k)
    c = _f32_limbs(c_vals)                   # (lc, P)
    errs = np.empty(a.shape[1])
    for s in range(0, a.shape[1], block):
        aa, bb = a[:, s:s + block], b[:, s:s + block]
        terms = aa[:, None] * bb[None, :]      # (la, lb, p, k): exact
        terms = terms.transpose(2, 0, 1, 3).reshape(aa.shape[1], -1)
        scale = np.abs(aa.sum(0) * bb.sum(0)).sum(-1)
        for t, (row, ct) in enumerate(zip(terms, c[:, s:s + block].T)):
            err = math.fsum(row.tolist() + (-ct).tolist())
            errs[s + t] = abs(err) / scale[t] if scale[t] else abs(err)
    return errs


def solve_errors(a_rows, x, b_vals, norm: float) -> np.ndarray:
    """Per sampled row i: |b_i - A_i x| / norm, with the residual summed
    exactly; ``norm`` is ||A||_inf ||x||_inf + ||b||_inf.

    ``a_rows``: limbs of A's sampled rows, each (P, n); ``x``: limbs of the
    solution, each (n,); ``b_vals``: limbs of b at those rows, each (P,).
    """
    a = _f32_limbs(a_rows)          # (la, P, n)
    xs = _f32_limbs(x)              # (lx, n)
    bv = _f32_limbs(b_vals)         # (lb, P)
    errs = np.empty(a.shape[1])
    for i in range(a.shape[1]):
        terms = (a[:, i][:, None] * xs[None]).ravel()  # exact
        r = math.fsum((-terms).tolist() + bv[:, i].tolist())
        errs[i] = abs(r) / norm
    return errs
