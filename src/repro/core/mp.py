"""Count-generic multi-limb arithmetic — one API over any limb count.

The precision ladder (DESIGN.md §8) has one rung per limb count: ``dd``
(2 limbs, ~106 mantissa bits over f64), ``td`` (3 limbs, ~159 bits) and
``qd`` (4 limbs, ~212 bits).  Algorithms above the arithmetic — blocked
LU, TRSM, Cholesky, the GEMM engine's pad/batch/shard plumbing, the Rgemm
epilogue — are identical at every rung; only the per-element ops differ.
This module is the seam in both directions:

  * **downward**, it owns the count-parametric limb-list kernel family
    (``renorm_list`` and the ``*_limbs`` recipes below, Priest/Hida-style
    expansions with CAMPARY branch-free renormalization).  Tier modules
    (``td.py``, ``qd.py``) are thin bindings of these recipes at a fixed
    count; ``dd.py`` keeps its specialized two-limb algorithms (Li add,
    Dekker mul, Karp sqrt) as the documented k == 2 fast path, bit-for-bit
    compatible with the generic family's contracts.
  * **upward**, it dispatches the tier-value API (``add``/``mul``/...) on
    the concrete value type, so callers are written once against ``mp.*``
    and gain every rung — including future ones — for free.  Adding a rung
    means: one entry in ``PRECISIONS``, one thin tier module.  No other
    layer may re-derive limb counts.

Two op families:

  * **arithmetic** (``add``/``mul``/``div``/``sqrt``/``sum_``/...) —
    forwarded to the tier module, which binds the generic recipes (or, for
    dd, its specialized EFT chains);
  * **structural** (``map_limbs``/``where``/``broadcast_to``/slicing) —
    applied limb-wise, since limbs are plain jnp arrays and shape surgery
    is precision-agnostic.

``PRECISIONS`` maps the plan-level precision names to limb counts; the GEMM
plan/autotune cache keys on the limb count so each tier tunes independently.
"""

from __future__ import annotations

import importlib
from typing import Sequence

import jax.numpy as jnp

from .efts import quick_two_sum, two_prod_terms, two_sum

__all__ = [
    "PRECISIONS", "nlimbs", "precision_of", "precision_for_count", "limbs",
    "from_limbs", "map_limbs", "from_float", "zeros", "to_float", "promote",
    "add", "sub", "neg", "abs_", "mul", "mul_float", "fma", "div", "sqrt",
    "where", "sum_", "dot", "broadcast_to", "eps", "max_abs", "is_zero",
    # count-generic limb-list kernels (tier modules bind these; kernels and
    # the Ozaki recombination distill through them directly)
    "renorm_list", "add_limbs", "neg_limbs", "mul_limbs", "mul_float_limbs",
    "mul_pow2_limbs", "fma_limbs", "div_limbs", "sqrt_limbs", "sum_limbs",
    "to_dd_limbs", "eps_for",
]

# precision name -> limb count, in ladder order (cheapest rung first).
# Each name resolves to a tier module of the same name in this package
# whose value type is the upper-cased name (dd.DD, td.TD, qd.QD).
PRECISIONS = {"dd": 2, "td": 3, "qd": 4}

_BY_COUNT = {n: name for name, n in PRECISIONS.items()}

_MODS: dict = {}


def _tier_mod(precision: str):
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; "
                         f"one of {sorted(PRECISIONS)}")
    mod = _MODS.get(precision)
    if mod is None:
        # lazy: tier modules import the generic kernels from here, so this
        # module must never import them at top level
        mod = importlib.import_module(f".{precision}", __package__)
        _MODS[precision] = mod
    return mod


def _tier_type(precision: str):
    return getattr(_tier_mod(precision), precision.upper())


def precision_for_count(n: int) -> str:
    """Precision name for a limb count (the inverse of ``PRECISIONS``)."""
    name = _BY_COUNT.get(n)
    if name is None:
        raise ValueError(f"unsupported limb count {n} "
                         f"(supported: {sorted(_BY_COUNT)})")
    return name


def precision_of(x) -> str:
    if isinstance(x, tuple) and hasattr(x, "limbs"):
        name = _BY_COUNT.get(len(x))
        if name is not None and isinstance(x, _tier_type(name)):
            return name
    raise TypeError(f"not a multi-limb value: {type(x).__name__}")


def _mod(x):
    return _tier_mod(precision_of(x))


def _mod2(a, *others):
    """Dispatch module for a binary/ternary op, rejecting mixed tiers.

    The count-generic limb kernels would happily concatenate a td and a qd
    limb list and renormalize to the FIRST operand's count — value-correct
    but a silent precision decision.  Mixing tiers must be an explicit
    ``promote``.  Non-tier operands (plain scalars/arrays) pass through for
    the tier module to coerce or reject itself.
    """
    pa = precision_of(a)
    for o in others:
        if isinstance(o, tuple) and hasattr(o, "limbs"):
            po = precision_of(o)
            if po != pa:
                raise TypeError(
                    f"mixed precision tiers: {pa!r} and {po!r} "
                    f"(mp.promote one operand explicitly)")
    return _tier_mod(pa)


def nlimbs(x) -> int:
    return PRECISIONS[precision_of(x)]


def limbs(x) -> list:
    """Limb arrays, most-significant first."""
    precision_of(x)  # type check
    return x.limbs()


def from_limbs(ls):
    """Rebuild a tier value from its limb list (count picks the tier)."""
    ls = list(ls)
    return _tier_type(precision_for_count(len(ls)))(*ls)


def map_limbs(f, x):
    """Apply a structural (shape-only) function to every limb."""
    return from_limbs([f(l) for l in limbs(x)])


def from_float(x, precision: str = "dd", dtype=None):
    return _tier_mod(precision).from_float(x, dtype=dtype)


def zeros(shape, precision: str = "dd", dtype=jnp.float64):
    return _tier_mod(precision).zeros(shape, dtype=dtype)


def to_float(x):
    return _mod(x).to_float(x)


def promote(x, precision: str):
    """Re-tier a value: climbing pads zero limbs (exact); descending
    distills the limb list to the narrower count (value-preserving sweeps,
    one rounding at the truncation — the multi-limb analogue of a
    round-to-nearest narrowing)."""
    kt = PRECISIONS.get(precision)
    if kt is None:
        raise ValueError(f"unknown precision {precision!r}; "
                         f"one of {sorted(PRECISIONS)}")
    cur = precision_of(x)
    if cur == precision:
        return x
    ls = limbs(x)
    if kt > len(ls):
        z = jnp.zeros_like(ls[0])
        return from_limbs(ls + [z] * (kt - len(ls)))
    return from_limbs(renorm_list(ls, k=kt))


def add(a, b):
    return _mod2(a, b).add(a, b)


def sub(a, b):
    return _mod2(a, b).sub(a, b)


def neg(a):
    return _mod(a).neg(a)


def abs_(a):
    return _mod(a).abs_(a)


def max_abs(a):
    """max |a| as an f64 scalar (the Rlange 'M' norm), traceable.

    The leading limb alone decides the magnitude ordering of a normalized
    expansion, and the lower limbs sit below its ulp — so the f64 value of
    the max-|entry| is exactly the max of |hi|.
    """
    return jnp.max(jnp.abs(limbs(a)[0]))


def is_zero(x):
    """Traced bool (elementwise): every limb of ``x`` is exactly zero.

    The single source for "is this tier value zero" — the engine's BLAS
    ``beta == 0`` guard and the fused kernel drain both key on it, so a
    future change to the zero encoding lands in one place.
    """
    z = None
    for l in limbs(x):
        e = l == 0
        z = e if z is None else jnp.logical_and(z, e)
    return z


def mul(a, b):
    return _mod2(a, b).mul(a, b)


def fma(acc, a, b):
    """acc + a*b — the multiply-add "PE" operation at acc's tier."""
    return _mod2(acc, a, b).fma(acc, a, b)


def mul_float(a, s):
    return _mod(a).mul_float(a, s)


def div(a, b):
    return _mod2(a, b).div(a, b)


def sqrt(a):
    return _mod(a).sqrt(a)


def where(c, a, b):
    return _mod(a).where(c, a, b)


def sum_(a, axis=None, keepdims=False):
    return _mod(a).sum_(a, axis=axis, keepdims=keepdims)


def dot(a, b):
    return _mod2(a, b).dot(a, b)


def broadcast_to(x, shape):
    return map_limbs(lambda l: jnp.broadcast_to(l, shape), x)


def eps_for(k: int, dtype=jnp.float64) -> float:
    """Unit roundoff of a k-limb expansion: 2^(-k*p) for p-bit limbs."""
    p = 53 if jnp.dtype(dtype) == jnp.float64 else 24
    return 2.0 ** (-k * p)


def eps(precision: str, dtype=jnp.float64) -> float:
    """Unit roundoff of a tier: 2^-2p (dd), 2^-3p (td), 2^-4p (qd)."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    return eps_for(PRECISIONS[precision], dtype)


# --------------------------------------------------------------------------
# Count-generic limb-list kernel family.
#
# Everything below operates on plain python lists of limb arrays (most-
# significant first) with the count inferred from the list length, and
# imports nothing above efts — the tier modules bind these at a fixed k.
# The recipes reduce exactly to the historical qd algorithms at k == 4
# (same EFT sequence, hence bit-identical results), and td (k == 3) is the
# proof that no recipe secretly assumes a count.
#
# We use CAMPARY-style *branch-free* renormalization (bottom-up two_sum
# sweeps followed by top-down compression) rather than the branchy
# QD-library renormalize: data-dependent branches do not vectorize in JAX.
# The sweeps are value-preserving (every step is an EFT); only the final
# truncation to k limbs rounds.  Per-count accuracy is property-tested
# (tests/test_qd.py, tests/test_td.py): observed ~2^-200 relative error
# for qd64 chains, ~2^-150 for td64 — both comfortably past their formats'
# nominal 2^(-k*53+53) working targets.
# --------------------------------------------------------------------------


def _vecsum_bottom_up(limbs: Sequence) -> list:
    """Bottom-up two_sum sweep: pushes the dominant mass into limb 0.

    Exact: the multiset of limbs keeps the same total value.
    """
    out = [None] * len(limbs)
    s = limbs[-1]
    for i in range(len(limbs) - 2, -1, -1):
        s, e = two_sum(limbs[i], s)
        out[i + 1] = e
    out[0] = s
    return out


def _compress_top_down(limbs: Sequence) -> list:
    """Top-down two_sum sweep: each error drops to the next slot. Exact."""
    acc = limbs[0]
    out = []
    for i in range(1, len(limbs)):
        acc, err = two_sum(acc, limbs[i])
        out.append(err)
    return [acc] + out


def renorm_list(terms: Sequence, k: int = 4, sweeps: int = 3) -> list:
    """Distill an arbitrary list of floats into a k-limb expansion.

    Alternating exact sweeps converge the list toward a non-overlapping
    expansion; after the final sweep the tail beyond k limbs is folded into
    limb k-1 with ordinary (rounding) adds.
    """
    limbs = list(terms)
    for _ in range(sweeps):
        limbs = _vecsum_bottom_up(limbs)
        limbs = _compress_top_down(limbs)
    head, tail = limbs[: k - 1], limbs[k - 1 :]
    last = tail[-1]
    for t in reversed(tail[:-1]):
        last = last + t
    head.append(last)
    # final canonicalizing pass
    head = _compress_top_down(_vecsum_bottom_up(head))
    return head


def add_limbs(al: Sequence, bl: Sequence) -> list:
    """k-limb + k-limb: distill the concatenated expansions."""
    al, bl = list(al), list(bl)
    return renorm_list(al + bl, k=len(al), sweeps=3)


def neg_limbs(al: Sequence) -> list:
    return [-l for l in al]


def mul_limbs(al: Sequence, bl: Sequence) -> list:
    """Sloppy k-limb multiply: exact partial products through O(eps^(k-1)).

    Limb products for orders < k-1 use the exact term decomposition
    (two_prod_terms) so the distilled result carries no two_prod slack;
    order-(k-1) terms are plain (inexact) products, which is fine at
    O(eps^k).
    """
    al, bl = list(al), list(bl)
    k = len(al)
    terms = []
    for i in range(k):
        for j in range(k):
            o = i + j
            if o < k - 1:
                terms.extend(two_prod_terms(al[i], bl[j]))
            elif o == k - 1:
                terms.append(al[i] * bl[j])
    return renorm_list(terms, k=k, sweeps=3)


def mul_float_limbs(al: Sequence, b) -> list:
    """k-limb * plain-float array.  Exact partial products through limb
    k-2, distilled; cheaper than lifting ``b`` to k limbs for a full
    ``mul_limbs``."""
    al = list(al)
    b = jnp.asarray(b, al[0].dtype)
    terms = []
    for l in al[:-1]:
        terms.extend(two_prod_terms(l, b))
    terms.append(al[-1] * b)
    return renorm_list(terms, k=len(al), sweeps=3)


def mul_pow2_limbs(al: Sequence, s) -> list:
    """Exact scaling by a power of two."""
    return [l * s for l in al]


def fma_limbs(acc: Sequence, al: Sequence, bl: Sequence) -> list:
    return add_limbs(list(acc), mul_limbs(al, bl))


def div_limbs(al: Sequence, bl: Sequence) -> list:
    """Long division at k limbs: k+1 native-quotient correction rounds.

    Each round contributes ~53 bits of quotient (q_i = r[0] / b[0], then
    the remainder is updated exactly-ish via ``mul_float_limbs``), so k+1
    rounds overshoot the k*53-bit format; the distilled q_i are the
    result.  Branch free, like everything in this module.
    """
    al, bl = list(al), list(bl)
    k = len(al)
    q_terms = []
    r = al
    for _ in range(k + 1):
        qi = r[0] / bl[0]
        q_terms.append(qi)
        r = add_limbs(r, neg_limbs(mul_float_limbs(bl, qi)))
    return renorm_list(q_terms, k=k, sweeps=3)


def to_dd_limbs(ls: Sequence):
    """(hi, lo) double-word rounding of a k-limb expansion."""
    ls = list(ls)
    s, e = quick_two_sum(ls[0], ls[1])
    if len(ls) > 2:
        tail = ls[2]
        for t in ls[3:]:
            tail = tail + t
        e = e + tail
    return quick_two_sum(s, e)


def sqrt_limbs(al: Sequence) -> list:
    """k-limb sqrt: DD seed (~106 bits) + one Heron step s <- (s + a/s)/2.

    Newton doubles the correct bits, so one step lands at ~212 — at or
    past the capacity of every supported count (k <= 4).  Zero is guarded
    (the seed's 1/sqrt would inf*0 -> nan).
    """
    from . import dd as _dd

    al = list(al)
    k = len(al)
    sd = _dd.sqrt(_dd.DD(*to_dd_limbs(al)))
    z = jnp.zeros_like(al[0])
    s0 = [sd.hi, sd.lo] + [z] * (k - 2)
    s = mul_pow2_limbs(add_limbs(s0, div_limbs(al, s0)), 0.5)
    zero = al[0] == 0
    return [jnp.where(zero, jnp.zeros_like(l), l) for l in s]


def sum_limbs(al: Sequence, axis=None, keepdims=False) -> list:
    """Compensated reduction along an axis by repeated halving (every
    partial stays a full k-limb expansion, mirroring dd.sum_)."""
    al = list(al)
    if axis is None:
        return sum_limbs([l.reshape(-1) for l in al], axis=0,
                         keepdims=keepdims)
    cur = [jnp.moveaxis(l, axis, 0) for l in al]
    m = cur[0].shape[0]
    while m > 1:
        if m % 2:
            # an odd count pairs its last term with an exact zero
            pad = [(0, 1)] + [(0, 0)] * (cur[0].ndim - 1)
            cur = [jnp.pad(l, pad) for l in cur]
            m += 1
        cur = add_limbs([l[0::2] for l in cur], [l[1::2] for l in cur])
        m //= 2
    out = [l[0] for l in cur]
    if keepdims:
        out = [jnp.expand_dims(l, axis) for l in out]
    return out
