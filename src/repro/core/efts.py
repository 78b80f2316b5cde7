"""Error-free transformations (EFTs) — the primitive "DSP blocks" of this port.

The paper composes binary128 multiply-add units out of FPGA DSP blocks.  On a
TPU the native units are f32 (VPU lanes) and bf16 (MXU); we compose wide
arithmetic out of them with error-free transformations:

  two_sum(a, b)        -> (s, e)  with  s = fl(a+b),  s + e == a + b  exactly
  quick_two_sum(a, b)  -> same, requires |a| >= |b| (3 ops instead of 6)
  two_prod(a, b)       -> (p, e)  with  p + e == a * b * (1 + eps_tp)

Compiler-safety design note (important, discovered empirically):
XLA:CPU's LLVM backend performs FMA *contraction* — a float multiply feeding
an add/subtract inside one fused loop may be emitted as a single fma, so the
add sees the UNROUNDED product.  Classic Dekker two_prod subtracts the
rounded ``p = fl(a*b)`` from partial products; if the compiler rematerializes
``a*b`` into that subtraction as an fma, the error term collapses.  The
implementation below is **contraction-robust by construction**: ``p`` is
assembled from the four *exact* partial products of Veltkamp splits with
two_sum chains, so every multiply that reaches an add is exactly
representable and fma contraction cannot change any value.

Lowering note: every operation here is plain float arithmetic, compares
and selects — no bit reinterpretation, ``frexp`` or ``ldexp``.  XLA cannot
lower 64-bit bitcasts for the TPU, so the split is Veltkamp's
(``t = C*a; hi = t - (t - a)``) rather than a mantissa mask, and powers of
two come from selects over exact constants.

Cost: two_prod is not bit-exact; its relative error is <= ~2^-2p+2
(2^-105 for f64 limbs, 2^-47 for f32), from rounding when summing the
three two_sum error terms.  Double-word arithmetic built on it keeps
relative error ~2^-104 / ~2^-46 — the same class as the binary128 target
(and as the paper's own DD-based related work).  Property tests pin these
bounds against Fraction oracles.

All algorithms assume round-to-nearest and flush-to-zero-free inputs in the
normal range (XLA:CPU flushes subnormals).

The premise check: every f64 EFT passes one value through an identity
primitive that lowers to nothing, except on the TPU, where it refuses the
program with ``TPU_F64_REFUSAL`` (XLA emulates f64 there, and the EFTs
are not error-free on it).  So dd/td/qd arithmetic over f64 limbs cannot
compile for a TPU, whatever path reaches it.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax.extend.core import Primitive
from jax.interpreters import ad, batching, mlir

__all__ = [
    "TPU_F64_REFUSAL",
    "premise_unchecked",
    "two_sum",
    "quick_two_sum",
    "split",
    "two_prod",
    "two_prod_terms",
    "two_prod_exact",
    "pow2_floor",
    "TWO_PROD_RELERR",
]

# relative error bound of two_prod per limb dtype (see module docstring)
TWO_PROD_RELERR = {
    jnp.dtype(jnp.float64): 2.0**-104,
    jnp.dtype(jnp.float32): 2.0**-46,
}

# Veltkamp splitter 2^s + 1 with s = ceil(p/2): hi keeps p - s bits and lo
# s - 1 bits plus sign, so every hi/lo cross product is exact.  Stored as
# the exact power 2^s (see split)
_SPLITTER = {
    jnp.dtype(jnp.float64): 2.0 ** 27,
    jnp.dtype(jnp.float32): 2.0 ** 12,
}


TPU_F64_REFUSAL = (
    "f64 limbs are refused on the TPU: XLA emulates f64 there on pairs of "
    "32-bit values, which have neither binary64's rounding nor its "
    "exponent range; on a v5e, chip_smoke.py's probe found f64 add and mul "
    "differing from binary64 even for operands inside 2^+-60, and two_sum "
    "/ two_prod inexact on 511 and 512 of 512 in-range pairs, so every "
    "f64-limb tier would return wrong finite numbers; use float32 limbs")


def _refuse_tpu(x):
    if any(d.platform == "tpu" for d in x.devices()):
        raise ValueError(TPU_F64_REFUSAL)
    return x


def _refuse_tpu_lowering(ctx, x):
    raise ValueError(TPU_F64_REFUSAL)


_f64_premise_p = Primitive("f64_eft_premise")
_f64_premise_p.def_impl(_refuse_tpu)
_f64_premise_p.def_abstract_eval(lambda aval: aval)
mlir.register_lowering(_f64_premise_p, lambda ctx, x: [x])
mlir.register_lowering(_f64_premise_p, _refuse_tpu_lowering, platform="tpu")
batching.primitive_batchers[_f64_premise_p] = \
    lambda args, dims: (_f64_premise_p.bind(*args), dims[0])
ad.primitive_jvps[_f64_premise_p] = \
    lambda primals, tangents: (_f64_premise_p.bind(*primals), tangents[0])

_CHECK_PREMISE = [True]


@contextlib.contextmanager
def premise_unchecked():
    """Trace f64 EFTs without the TPU refusal.

    For probing what a device's f64 does (``chip_smoke.py``), never for
    computing with it.  Only traces made inside the block drop the check,
    so jit a fresh function there.
    """
    _CHECK_PREMISE[0] = False
    try:
        yield
    finally:
        _CHECK_PREMISE[0] = True


def _premise(x):
    """x, marked as resting on the f64 EFT premise (see module docstring)."""
    if _CHECK_PREMISE[0] and isinstance(x, jax.Array) \
            and x.dtype == jnp.float64:
        return _f64_premise_p.bind(x)
    return x


def two_sum(a, b):
    """Knuth's branch-free exact addition: s + e == a + b exactly."""
    s = _premise(a + b)
    bb = s - a
    e = (a - (s - bb)) + (b - bb)
    return s, e


def quick_two_sum(a, b):
    """Dekker's fast exact addition. Exact when |a| >= |b| (or a == 0)."""
    s = _premise(a + b)
    e = b - (s - a)
    return s, e


def split(a):
    """Veltkamp split: a == hi + lo exactly, each half ~p/2 bits.

    f64: hi 26 bits, lo 26 bits (sign included); f32: 12 and 12, so all
    four cross products of two splits are exact.  ``t = fl((2^s + 1) a)``
    is formed as ``a * 2^s + a``: the product is exact, so a compiler
    that contracts it into an fma gets the same t, and no multiply is
    left to feed ``t - a`` (contracting THAT would change hi, and with it
    the last bits of every product).  ``t`` must not overflow: callers
    keep |a| inside the rescue band (see _RESCUE).
    """
    c = _SPLITTER.get(jnp.dtype(a.dtype))
    if c is None:
        raise ValueError(f"unsupported limb dtype {a.dtype}")
    t = _premise(a * c + a)
    hi = t - (t - a)
    return hi, a - hi


# Scale-aware operand rescue (extreme-scale exactness).  The split's low
# part has magnitude down to 2^(e-53); for |a| below ~2^-970 it lands in
# the subnormal range, which XLA:CPU flushes to zero — silently losing the
# m2/m4 partial products (measured: up to ~2^-25 relative error on dd.mul
# for operand pairs like 2^1005 x 2^-1005 whose PRODUCT is perfectly
# representable).  At the top of the range the splitter product C*a
# overflows.  The rescue pre-scales each operand by an exact power of two
# into a safe band and unscales the result; in the normal band the factor
# is exactly 1.0, so in-range results are bit-identical to the unscaled
# computation.
#
# Band arithmetic (f64; f32 analogous with p=24, emax=127):
#   * operands with |x| < 2^-484 scale UP by 2^512, |x| > 2^484 scale DOWN
#     by 2^-512;
#   * every reachable scaled-exponent pair sum lies in [-968, 1023], so no
#     partial product overflows and none is flushed beyond its ordinary
#     <= 1/2 ulp rounding allowance (pairs summing below -968 have
#     products whose dd tail is sub-representable anyway — inherent);
#   * the rescue scales ride along as INTEGER exponents, so unscaling sums
#     them first — an up-rescue and a down-rescue cancel to 0 before any
#     float factor exists.  (Applying the inverse *factors* in any fixed
#     order is wrong: for a huge x tiny pair whose product is large but
#     representable, e.g. 2^1020 x 2^-485, the >1-first order sends the
#     2^535-scale intermediate through 2^1047 = Inf.)  A same-direction
#     residual of +-2*shift is applied as two normal-range half factors,
#     since 2^{+-1024} is not itself representable.
_RESCUE = {
    jnp.dtype(jnp.float64): (2.0 ** -484, 2.0 ** 484, 2.0 ** 512, 512),
    jnp.dtype(jnp.float32): (2.0 ** -60, 2.0 ** 60, 2.0 ** 64, 64),
}


def _rescue(x):
    """(x * s, e) with s = 2^-e an exact pow2 moving x into the safe band.

    The returned e is the integer UNSCALE exponent (x*s * 2^e == x * 2^0
    scale-wise); e == 0 for in-band operands, where s == 1 exactly.
    NaN/Inf/0 pass through (the comparisons are False on NaN, Inf scales
    down but stays Inf, 0 scales up and stays 0).
    """
    tiny, huge, up, shift = _RESCUE[jnp.dtype(x.dtype)]
    ax = jnp.abs(x)
    one = jnp.ones_like(x)  # keeps the factor in x's dtype
    # 1/up is formed on the host, exactly (a power of two), so no device
    # division enters the EFTs
    s = jnp.where(ax < tiny, up * one,
                  jnp.where(ax > huge, one * (1.0 / up), one))
    e = jnp.where(ax < tiny, jnp.int32(-shift),
                  jnp.where(ax > huge, jnp.int32(shift), jnp.int32(0)))
    return x * s, e


def _unscale(x, ea, eb):
    """x * 2^(ea+eb) with opposite-direction rescues cancelling exactly.

    The integer sum ea+eb is formed before any float factor, so a mixed
    huge x tiny pair unscales by 2^0 == 1 and never transits Inf or the
    flushed subnormal range.  A same-direction sum (|ea+eb| = 2*shift,
    whose single factor would be unrepresentable) is applied as two exact
    normal-range halves.  The sum takes only the values {0, +-shift,
    +-2*shift}, so each half factor is a select over exact constants.
    """
    shift = _RESCUE[jnp.dtype(x.dtype)][3]
    e = ea + eb
    h = e // jnp.int32(2)  # {0, +-shift/2, +-shift}; so is e - h
    one = jnp.ones_like(x)

    def pow2(k):
        f = one
        for v in (shift // 2, shift):
            f = jnp.where(k == v, one * 2.0 ** v,
                          jnp.where(k == -v, one * 2.0 ** -v, f))
        return f

    return x * pow2(h) * pow2(e - h)


def two_prod(a, b):
    """Near-exact multiplication: p + e == a*b up to TWO_PROD_RELERR[dtype].

    The four partial products of the Veltkamp splits are exactly
    representable, so assembling them with two_sum chains is immune to fma
    contraction (see module docstring).  ``p`` is within 1 ulp of fl(a*b).
    Operands are pow2-rescued into the safe exponent band first, so the
    bound holds out to the edges of the representable range (see _RESCUE);
    in-band operands compute bit-identically to the unscaled algorithm.
    """
    (m1, m2, m3, m4), ea, eb = _scaled_terms(a, b)
    s, e1 = two_sum(m1, m2)
    s, e2 = two_sum(s, m3)
    s, e3 = two_sum(s, m4)
    e = e1 + (e2 + e3)
    return _unscale(s, ea, eb), _unscale(e, ea, eb)


def two_prod_terms(a, b):
    """a*b as a list of floats summing to the product EXACTLY.

    Every returned term is an exactly-representable product of split
    halves, keeping the decomposition both exact and fma-contraction-proof.
    Used by the quad-word layer, where two_prod's 2^-105 slack would
    dominate the error budget.  Operands get the same pow2 rescue as
    two_prod (each term is unscaled individually — exact, since the
    factors are powers of two), so the decomposition stays exact out to
    the edges of the representable range.
    """
    terms, ea, eb = _scaled_terms(a, b)
    return [_unscale(t, ea, eb) for t in terms]


def _scaled_terms(a, b):
    """Exact product terms of the rescued operands, plus unscale exponents."""
    a, ea = _rescue(a)
    b, eb = _rescue(b)
    ah, al = split(a)
    bh, bl = split(b)
    return [ah * bh, ah * bl, al * bh, al * bl], ea, eb


def two_prod_exact(a, b):
    """Exact two_prod: p + e == a*b exactly (distilled from exact terms).

    Distills in the rescued exponent band and unscales only the final
    (p, e) pair: unscaling the raw terms individually could flush a small
    term that the distilled error limb would have absorbed losslessly.
    """
    terms, ea, eb = _scaled_terms(a, b)
    for _ in range(3):  # vecsum sweeps converge the fixed-size expansion
        out = [None] * len(terms)
        s = terms[-1]
        for i in range(len(terms) - 2, -1, -1):
            s, err = two_sum(terms[i], s)
            out[i + 1] = err
        out[0] = s
        terms = out
    # fold the (now far-below-ulp^2) tail exactly into the second limb
    e = terms[1]
    for t in terms[2:]:
        e, r = two_sum(e, t)
        # r is zero after convergence; add it anyway to keep exactness
        e = e + r
    p, e = quick_two_sum(terms[0], e)
    return _unscale(p, ea, eb), _unscale(e, ea, eb)


def pow2_floor(x):
    """2^floor(log2 x) for positive normal x, exactly; NaN where x is not
    finite.

    A greedy binary search over exact power-of-two constants: each step
    rescales the running mantissa by 2^(+-2^j) when it lies that far
    outside [1, 2), so every multiply is exact and the result needs no
    ``frexp``, ``log2`` or bit reinterpretation.
    """
    top = 9 if jnp.dtype(x.dtype) == jnp.float64 else 6  # 2^(2^top) < max
    m, p = x, jnp.ones_like(x)
    for j in range(top, -1, -1):
        big, small = 2.0 ** (2 ** j), 2.0 ** -(2 ** j)
        up = m >= big
        down = m < 2.0 * small
        m = jnp.where(up, m * small, jnp.where(down, m * big, m))
        p = jnp.where(up, p * big, jnp.where(down, p * small, p))
    return jnp.where(jnp.isfinite(x), p, p * jnp.nan)
