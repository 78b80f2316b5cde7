"""Accuracy regression oracle: exact-rational Hilbert-matrix GEMM.

The paper validates its FPGA GEMM against a CPU Rgemm reference (Eq. 6);
here each precision tier is validated against an *exact* reference instead.
The Hilbert matrix H_ij = 1/(i+j+1) (maximally ill-conditioned, the classic
extended-precision stress case) is formed IN the tier's own arithmetic — a
multi-limb division, so every limb carries signal and the product genuinely
rounds at the tier's precision — and H @ H is then evaluated in exact
rational arithmetic (``fractions.Fraction``) over those representable
multi-limb entries.  The observed relative error of each tier's engine
output against that oracle is the quantity the regression gate pins:

    dd (2 limbs, ~106-bit)  must stay <= 2^-100
    td (3 limbs, ~159-bit)  must stay <= 2^-150
    qd (4 limbs, ~212-bit)  must stay <= 2^-190

``benchmarks/bench_accuracy.py`` emits the same numbers to
``BENCH_ACCURACY.json`` (uploaded by CI) so the accuracy trajectory is
machine-readable across commits; tests/test_accuracy_gate.py asserts the
thresholds in tier 1.
"""

from __future__ import annotations

import functools
import json
import time
from fractions import Fraction

import jax.numpy as jnp
import numpy as np

from . import mp

__all__ = ["GATES", "GATED_BACKENDS", "hilbert_f64",
           "hilbert_relative_error", "accuracy_report",
           "write_accuracy_json", "max_rel_err", "tier_ulp",
           "frac_matrix", "frac_matmul", "frac_sub", "frac_max_abs"]

# per-tier observed-relative-error ceilings (the regression gate).  The
# expected error class is a few ulp of the tier (2^-104 / 2^-155 / 2^-206
# for dd / td / qd); each gate leaves a handful of bits of headroom so the
# gate trips on real regressions, not on reduction-order jitter.
GATES = {"dd": 2.0 ** -100, "td": 2.0 ** -150, "qd": 2.0 ** -190}

# backends pinned by the gate, with the tiers each one supports: the
# engine default (xla) plus both Ozaki slicing paths — the whole-K
# diagonal-grouped XLA recombination (dd/td; qd is planner-rejected) and
# the per-slab fused Pallas kernel (every tier)
GATED_BACKENDS = {
    "xla": ("dd", "td", "qd"),
    "ozaki": ("dd", "td"),
    "ozaki-pallas": ("dd", "td", "qd"),
}


def hilbert_f64(n: int) -> np.ndarray:
    """Hilbert matrix H_ij = 1/(i+j+1), rounded once to f64."""
    i = np.arange(n, dtype=np.float64)
    return 1.0 / (i[:, None] + i[None, :] + 1.0)


# bits short of k*p that one engine FMA loses per tier (dd: two_prod's
# slack dominates; td/qd: the O(eps^k) renormalization truncation)
_ULP_DEFICIT = {"dd": 2, "td": 4, "qd": 7}


def tier_ulp(precision: str, limb_dtype=jnp.float64) -> float:
    """Per-FMA unit roundoff of a tier over its limbs: the conformance
    bound is 16 * k * tier_ulp (2^-104 / 2^-155 / 2^-205 over f64 limbs,
    2^-46 / 2^-68 / 2^-89 over f32)."""
    p = 53 if jnp.dtype(limb_dtype) == jnp.float64 else 24
    return 2.0 ** -(mp.PRECISIONS[precision] * p - _ULP_DEFICIT[precision])


def max_rel_err(got, want) -> float:
    """Max |got - want| / max(1, max|want|), measured in the values' tier.

    The shared conformance metric: the smoke benchmark, the conformance
    matrix, and the kernel tests all gate on this one definition.
    """
    diff = np.abs(np.asarray(mp.to_float(mp.sub(got, want)), np.float64))
    scale = max(1.0, float(np.abs(np.asarray(mp.to_float(want))).max()))
    return float(diff.max()) / scale


def hilbert_tier(precision: str, n: int):
    """Hilbert matrix formed in tier arithmetic: every limb carries signal."""
    i = jnp.arange(n, dtype=jnp.float64)
    denom = i[:, None] + i[None, :] + 1.0
    one = mp.from_float(jnp.ones((n, n)), precision)
    return mp.div(one, mp.from_float(denom, precision))


def _frac(limbs_np, i: int, j: int) -> Fraction:
    return sum((Fraction(float(l[i, j])) for l in limbs_np), Fraction(0))


# -- exact-rational matrix helpers (the LAPACK-grade residual gates) --------
#
# A multi-limb value is a finite sum of binary floats, hence an exact
# rational; residuals like PA - LU measured over Fractions carry zero
# measurement noise, so the test gates in tests/test_linalg_gates.py pin
# the factorization's *own* backward error and nothing else.


def frac_matrix(x):
    """Exact rational entries of a 2-D multi-limb value."""
    ls = [np.asarray(l, np.float64) for l in mp.limbs(x)]
    m, n = ls[0].shape
    return [[_frac(ls, i, j) for j in range(n)] for i in range(m)]


def frac_matmul(fa, fb):
    """Exact rational product of two Fraction matrices."""
    inner = len(fb)
    cols = len(fb[0])
    return [[sum((fa[i][k] * fb[k][j] for k in range(inner)), Fraction(0))
             for j in range(cols)] for i in range(len(fa))]


def frac_sub(fa, fb):
    return [[x - y for x, y in zip(ra, rb)] for ra, rb in zip(fa, fb)]


def frac_max_abs(f) -> float:
    """max |entry| of a Fraction matrix, rounded once to f64 at the end."""
    return float(max(abs(e) for row in f for e in row))


@functools.lru_cache(maxsize=8)
def _hilbert_oracle(precision: str, n: int):
    """Exact rational H @ H over the tier's representable H entries.

    Depends only on (precision, n) — NOT on the backend under test — and
    the O(n^3) Fraction arithmetic dominates gate wall time, so it is
    computed once and shared by every gated backend's cell.
    """
    x = hilbert_tier(precision, n)
    in_limbs = [np.asarray(l, np.float64) for l in mp.limbs(x)]
    fx = [[_frac(in_limbs, i, j) for j in range(n)] for i in range(n)]
    return [[sum((fx[i][k] * fx[k][j] for k in range(n)), Fraction(0))
             for j in range(n)] for i in range(n)]


def hilbert_relative_error(precision: str = "dd", n: int = 16,
                           backend: str = "xla") -> float:
    """Max observed relative error of one engine tier on H @ H vs the exact
    rational product of the tier's own (representable) H entries."""
    from repro.gemm import matmul

    x = hilbert_tier(precision, n)
    got = matmul(x, x, backend=backend)
    out_limbs = [np.asarray(l, np.float64) for l in mp.limbs(got)]
    want = _hilbert_oracle(precision, n)
    worst = 0.0
    for i in range(n):
        for j in range(n):
            rel = abs(float((_frac(out_limbs, i, j) - want[i][j])
                            / want[i][j]))
            worst = max(worst, rel)
    return worst


def accuracy_report(n: int = 16, backend: str = "xla",
                    tiers=None) -> dict:
    """Observed relative error per tier, with its gate and headroom."""
    out = {}
    for prec in (tiers if tiers is not None else GATES):
        gate = GATES[prec]
        err = hilbert_relative_error(prec, n=n, backend=backend)
        out[prec] = {
            "rel_err": err,
            "gate": gate,
            "log2_err": float(np.log2(err)) if err > 0 else None,
            "passes": bool(err <= gate),
        }
    return out


def write_accuracy_json(path: str, n: int = 16, backend: str = "xla") -> dict:
    """Emit the per-tier accuracy artifact (schema repro-accuracy/v2).

    ``tiers`` keeps the primary backend's per-tier rows (the v1 layout);
    ``backends`` adds one such block per gated backend, so a slicing-path
    regression is visible in the artifact even when the default engine
    path still passes.
    """
    import jax

    backends = {
        be: accuracy_report(n=n, backend=be, tiers=supported)
        for be, supported in GATED_BACKENDS.items()
    }
    # the legacy per-tier block aliases the primary backend's rows when it
    # is gated with the full tier set (the common case); a partially-gated
    # primary (e.g. dd-only ozaki) reports only the tiers it supports
    tiers = backends[backend] \
        if set(GATED_BACKENDS.get(backend, ())) == set(GATES) \
        else accuracy_report(n=n, backend=backend,
                             tiers=GATED_BACKENDS.get(backend))
    doc = {
        "schema": "repro-accuracy/v2",
        "unix_time": time.time(),
        "platform": jax.default_backend(),
        "case": {"matrix": "hilbert", "n": n, "backend": backend,
                 "backends": sorted(GATED_BACKENDS)},
        "tiers": tiers,
        "backends": backends,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return doc
