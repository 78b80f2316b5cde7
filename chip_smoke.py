"""Chip smoke test: the extended-precision GEMM engine and the blocked LU on
one TPU, checked against exact rational references.

    python chip_smoke.py              # one chip: probe, GEMM, LU
    python chip_smoke.py --chips 4    # 2x2 mesh: SUMMA GEMM vs one chip

Phases (one process, no children):

  1. probe — ``efts.two_sum`` / ``efts.two_prod`` on the device over seeded
     inputs spread across exponents (out to the edges of the normal
     range), compared bit for bit with the same operations in numpy and
     checked for exactness with ``fractions``.  It decides which limb
     dtypes hold the error-free-transformation premise on this chip.
  2. GEMM — n^3 through ``repro.gemm.matmul`` under the default plan, for
     each tier over each limb dtype that holds.
  3. LU — ``rgetrf`` + ``lu_solve`` at the GEMM's dd tier.

Each phase samples its result (64 entries of C; 8 rows of the solve
residual b - A x) and compares it with an exact reference computed on the
host from the limbs.  The bound is the conformance matrix's:
16 * k * tier_ulp (``repro.core.accuracy.tier_ulp``).  Backend failover
and the slicing-infeasible fallback are errors here, the plan cache is
fresh, and the plan that ran must be the plan that was made.

The last line of standard output is ``{"ok": true, "device": {...}}``; it
is printed only when every phase passed on a TPU.  The times printed are
smoke times of one run, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import warnings
from fractions import Fraction
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "build" / "chip_smoke"  # gitignored; holds the fresh plan cache
SEED = 20230607
N = 4096          # one chip: GEMM n^3 and LU n x n
N_SUMMA = 8192    # 2x2 mesh: each chip holds a 4096^2 block
LU_BLOCK = 512    # panel width: 8 panels, so 8 shapes to compile


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# exact host references
# ---------------------------------------------------------------------------


def _scaled_ints(values, shift: int):
    """Exact integers v * 2^shift for binary floats v (shift >= -min exp)."""
    out = []
    for v in values:
        num, den = float(v).as_integer_ratio()
        out.append(num << (shift - den.bit_length() + 1))
    return out


def _limb_ints(limbs, shift: int):
    """Elementwise exact sum of a value's limbs, as scaled integers."""
    cols = [_scaled_ints(np.ravel(l), shift) for l in limbs]
    return [sum(t) for t in zip(*cols)]


def _shift_for(dtype) -> int:
    return 1074 if np.dtype(dtype) == np.float64 else 149


# ---------------------------------------------------------------------------
# phase 1: does the device round as IEEE binary64 / binary32?
# ---------------------------------------------------------------------------


def _np_efts(dtype):
    """numpy mirror of efts.two_sum / efts.two_prod, flushing subnormal
    results to zero as XLA does on the CPU and the TPU."""
    from repro.core import efts

    dt = np.dtype(dtype)
    tiny_normal = np.finfo(dt).tiny
    r_tiny, r_huge, r_up, r_shift = efts._RESCUE[dt]
    c = dt.type(efts._SPLITTER[dt])

    def f(x):
        return np.where(np.abs(x) < tiny_normal, x * dt.type(0), x)

    def two_sum(a, b):
        s = f(a + b)
        bb = f(s - a)
        return s, f(f(a - f(s - bb)) + f(b - bb))

    def rescue(x):
        ax = np.abs(x)
        s = np.where(ax < r_tiny, r_up, np.where(ax > r_huge, 1 / r_up, 1.0))
        e = np.where(ax < r_tiny, -r_shift,
                     np.where(ax > r_huge, r_shift, 0)).astype(np.int32)
        return f(x * s.astype(dt)), e

    def split(x):
        t = f(f(x * c) + x)
        hi = f(t - f(t - x))
        return hi, f(x - hi)

    def unscale(x, ea, eb):
        e = ea + eb
        h = e // 2
        p1 = np.ldexp(np.ones_like(x), h)
        p2 = np.ldexp(np.ones_like(x), e - h)
        return f(f(x * p1) * p2)

    def two_prod(a, b):
        a, ea = rescue(a)
        b, eb = rescue(b)
        ah, al = split(a)
        bh, bl = split(b)
        m1, m2, m3, m4 = f(ah * bh), f(ah * bl), f(al * bh), f(al * bl)
        s, e1 = two_sum(m1, m2)
        s, e2 = two_sum(s, m3)
        s, e3 = two_sum(s, m4)
        e = f(e1 + f(e2 + e3))
        return unscale(s, ea, eb), unscale(e, ea, eb)

    return two_sum, two_prod


def _probe_inputs(dtype, rng, n: int = 4096):
    """Seeded pairs over the whole normal exponent range, plus the edges."""
    fi = np.finfo(dtype)
    emin, emax = fi.minexp, fi.maxexp - 1
    e = rng.integers(emin, emax + 1, (2, n))
    m = rng.uniform(1, 2, (2, n)) * rng.choice([-1.0, 1.0], (2, n))
    a, b = (np.ldexp(m, e)).astype(dtype)
    edges = np.array([fi.max, -fi.max, fi.tiny, -fi.tiny, fi.max / 3,
                      fi.tiny * 3, 1.0, 1.0 + fi.eps, 1.0 - fi.eps / 2,
                      np.pi, -np.e], dtype)
    a = np.concatenate([a, edges, edges])
    b = np.concatenate([b, edges[::-1], np.roll(edges, 3)])
    return a, b


def _same(x, y) -> np.ndarray:
    return (x == y) | (np.isnan(x) & np.isnan(y))


def _probe_dtype(efts, dtype, rng) -> bool:
    name = np.dtype(dtype).name
    tiny = np.finfo(dtype).tiny
    a, b = _probe_inputs(dtype, rng)
    np_sum, np_prod = _np_efts(dtype)

    def flush(*outs):
        # results modulo flush-to-zero: a product that rounds UP to the
        # smallest normal is flushed by XLA (tininess before rounding) but
        # kept by numpy, so both sides drop |x| <= tiny
        return np.concatenate([np.where(np.abs(o) <= tiny, 0 * o, o)
                               for o in map(np.asarray, outs)])

    ops = {
        "roundtrip": lambda x, y: x,
        "add": jnp.add,
        "mul": jnp.multiply,
        "two_sum": lambda x, y: efts.two_sum(x, y),
        "two_prod": lambda x, y: efts.two_prod(x, y),
    }
    try:
        # the device's own f64 is what is measured here, so the premise
        # check that refuses f64 EFTs on the TPU is left out of these
        # traces (fresh lambdas: nothing else reuses them)
        with efts.premise_unchecked():
            raw = {k: jax.device_get(jax.jit(fn)(jnp.asarray(a),
                                                 jnp.asarray(b)))
                   for k, fn in ops.items()}
    except Exception as e:  # noqa: BLE001 — a device that cannot run the
        # EFTs at all fails the premise; say why and go on
        log(f"probe {name}: FAILS the EFT premise, the device cannot run "
            f"it ({type(e).__name__}: {str(e).splitlines()[0][:200]})")
        return False
    with np.errstate(all="ignore"):
        want = {
            "roundtrip": (a,),
            "add": np_sum(a, b)[:1],
            "mul": (a * b,),
            "two_sum": np_sum(a, b),
            "two_prod": np_prod(a, b),
        }
        got = {k: (flush(*(raw[k] if isinstance(raw[k], tuple)
                           else (raw[k],))), flush(*want[k]))
               for k in ops}
    # mismatches overall and where neither operand nor result leaves
    # 2^+-120: there a wrong bit is rounding, not exponent range
    band = (np.abs(np.log2(np.abs(a))) < 60) & (np.abs(np.log2(np.abs(b))) < 60)
    mism, mism_band = {}, {}
    for k, (g, w) in got.items():
        bad = ~_same(g, w)
        mism[k] = int(np.sum(bad))
        mism_band[k] = int(np.sum(bad & np.tile(band, bad.size // band.size)))
    s, e = np.split(got["two_sum"][0], 2)
    p, q = np.split(got["two_prod"][0], 2)

    def exact(v):  # a non-finite device value is not exact
        return Fraction(float(v)) if np.isfinite(v) else None

    # exactness where the premise claims it: in-range operands whose
    # sums/products and error terms stay normal
    lim = {np.float64: 450, np.float32: 45}[dtype]
    ok = (np.abs(np.log2(np.abs(a))) < lim) & (np.abs(np.log2(np.abs(b))) < lim)
    idx = np.flatnonzero(ok)[:512]
    tol = Fraction(efts.TWO_PROD_RELERR[np.dtype(dtype)])
    sum_bad = prod_bad = 0
    for i in idx:
        fa, fb = Fraction(float(a[i])), Fraction(float(b[i]))
        fs, fe, fp, fq = map(exact, (s[i], e[i], p[i], q[i]))
        sum_bad += None in (fs, fe) or fs + fe != fa + fb
        prod_bad += None in (fp, fq) or \
            abs(fp + fq - fa * fb) > tol * abs(fa * fb)
    holds = not any(mism.values()) and not sum_bad and not prod_bad
    log(f"probe {name}: {'HOLDS' if holds else 'FAILS'} the EFT premise "
        f"(n={a.size}; mismatches vs numpy {mism}, of them with operands "
        f"inside 2^+-60: {mism_band}; two_sum inexact {sum_bad}/"
        f"{idx.size}, two_prod beyond 2^{math.log2(float(tol)):.0f} "
        f"{prod_bad}/{idx.size})")
    return holds


def probe(efts) -> list:
    """The limb dtypes whose device arithmetic holds the EFT premise,
    widest first."""
    rng = np.random.default_rng(SEED)
    return [np.dtype(dt).name for dt in (np.float64, np.float32)
            if _probe_dtype(efts, dt, rng)]


# ---------------------------------------------------------------------------
# operands and sampled exact checks
# ---------------------------------------------------------------------------


def random_tier(key, shape, precision, dtype, uniform=False):
    """Seeded multi-limb operand with signal in every limb, made on device."""
    from repro.core import mp

    p = 53 if np.dtype(dtype) == np.float64 else 24
    keys = jax.random.split(key, mp.PRECISIONS[precision])
    draw = jax.random.uniform if uniform else jax.random.normal
    out = mp.from_float(draw(keys[0], shape, dtype), precision)
    for i, k in enumerate(keys[1:], start=1):
        out = mp.add(out, mp.from_float(
            draw(k, shape, dtype) * jnp.asarray(2.0 ** (-i * p), dtype),
            precision))
    return out


def tier_bound(precision, dtype, k: int) -> float:
    from repro.core.accuracy import tier_ulp

    return 16 * k * tier_ulp(precision, dtype)


def check_product(a, b, c, rows, cols, dtype) -> float:
    """max |C_ij - exact(A_i . B_j)| / max(1, max |exact|) on the sample."""
    from repro.core import mp

    sh = _shift_for(dtype)
    uniq_r, uniq_c = sorted(set(rows)), sorted(set(cols))
    a_rows = [np.asarray(l[np.asarray(uniq_r)]) for l in mp.limbs(a)]
    b_cols = [np.asarray(l[:, np.asarray(uniq_c)]) for l in mp.limbs(b)]
    c_s = [np.asarray(l[np.asarray(rows), np.asarray(cols)])
           for l in mp.limbs(c)]
    ar = {r: _limb_ints([l[t] for l in a_rows], sh)
          for t, r in enumerate(uniq_r)}
    bc = {q: _limb_ints([l[:, t] for l in b_cols], sh)
          for t, q in enumerate(uniq_c)}
    errs, scale = [], Fraction(1)
    for t, (i, j) in enumerate(zip(rows, cols)):
        exact = Fraction(sum(x * y for x, y in zip(ar[i], bc[j])),
                         2 ** (2 * sh))
        got = sum((Fraction(float(l[t])) for l in c_s), Fraction(0))
        errs.append(abs(got - exact))
        scale = max(scale, abs(exact))
    return float(max(errs) / scale)


def check_solve(a, x, b, rows, dtype) -> float:
    """max_i |b_i - A_i x| / (||A||_inf ||x||_inf + ||b||_inf), exactly."""
    from repro.core import mp

    sh = _shift_for(dtype)
    xs = _limb_ints([np.asarray(l)[:, 0] for l in mp.limbs(x)], sh)
    a_rows = [np.asarray(l[np.asarray(rows)]) for l in mp.limbs(a)]
    b_rows = [np.asarray(l[np.asarray(rows)]) for l in mp.limbs(b)]
    worst = Fraction(0)
    for t in range(len(rows)):
        ai = _limb_ints([l[t] for l in a_rows], sh)
        bi = _limb_ints([l[t] for l in b_rows], sh)[0]
        r = Fraction(bi, 2 ** sh) - Fraction(
            sum(p * q for p, q in zip(ai, xs)), 2 ** (2 * sh))
        worst = max(worst, abs(r))
    hi = [np.asarray(mp.limbs(v)[0], np.float64) for v in (a, x, b)]
    norm = np.abs(hi[0]).sum(axis=1).max() * np.abs(hi[1]).max() + \
        np.abs(hi[2]).max()
    return float(worst) / float(norm)


def _timed(fn, *args):
    """(result, first-call s, warm-call s): first includes compilation."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    t1 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    t2 = time.perf_counter()
    return out, t1 - t0, t2 - t1


def _report(name, err, bound, first, warm, plan=None):
    bits = "exact" if err == 0 else f"{-math.log2(err):.1f}"
    extra = ""
    if plan is not None:
        extra = (f" backend={plan.backend} interpret={plan.interpret} "
                 f"blocks=({plan.bm},{plan.bn},{plan.bk}) "
                 f"tier={plan.precision}/{plan.limb_dtype} "
                 f"slices={plan.n_slices} beta={plan.slice_beta}")
    log(f"{name}:{extra} compile_s={first - warm:.2f} run_s={warm:.3f} "
        f"sample_err={err:.3e} ({bits} bits) bound={bound:.3e}")
    if not err <= bound:
        raise AssertionError(f"{name}: sampled error {err:.3e} exceeds "
                             f"the tier bound {bound:.3e}")


def _assert_ran_as_planned(plan):
    """Failover warns (an error here) and quarantines; neither happened."""
    from repro.gemm import cache as plan_cache

    assert plan.backend != "ref" and plan.interpret == (
        plan.platform != "tpu"), plan
    q = plan_cache.quarantined(plan.platform, plan.backend, plan.nlimbs)
    assert q is None, f"backend {plan.backend} was quarantined: {q}"


# ---------------------------------------------------------------------------
# phases 2-3 (one chip) and the SUMMA phase (four chips)
# ---------------------------------------------------------------------------


def gemm_phase(n, precision, dtype, key):
    from repro import gemm

    a = random_tier(jax.random.fold_in(key, 1), (n, n), precision, dtype)
    b = random_tier(jax.random.fold_in(key, 2), (n, n), precision, dtype)
    plan = gemm.make_plan(n, n, n, dtype=dtype, precision=precision)
    c, first, warm = _timed(lambda x, y: gemm.matmul(x, y, plan=plan), a, b)
    _assert_ran_as_planned(plan)
    rng = np.random.default_rng(SEED + n)
    rows, cols = rng.integers(0, n, 64).tolist(), rng.integers(0, n, 64)
    err = check_product(a, b, c, rows, cols.tolist(), dtype)
    _report(f"gemm n={n}", err, tier_bound(precision, dtype, n), first,
            warm, plan)


def lu_phase(n, precision, dtype, key, block):
    from repro import gemm
    from repro.core.linalg import lu_solve, rgetrf

    a = random_tier(jax.random.fold_in(key, 3), (n, n), precision,
                    dtype, uniform=True)
    b = random_tier(jax.random.fold_in(key, 4), (n, 1), precision, dtype)

    # the default plan of the first trailing update; plans are shape-free,
    # so every shrinking update of the sweep runs this one
    plan = gemm.make_plan(n - block, block, n - block, dtype=dtype,
                          precision=precision)

    def solve(a, b):
        lu, piv = rgetrf(a, block=block, plan=plan)
        return lu_solve(lu, piv, b)

    x, first, warm = _timed(solve, a, b)
    _assert_ran_as_planned(plan)
    rows = np.random.default_rng(SEED + 1).integers(0, n, 8).tolist()
    err = check_solve(a, x, b, rows, dtype)
    _report(f"lu n={n} block={block}", err,
            tier_bound(precision, dtype, n), first, warm, plan)


def summa_phase(n, precision, dtype, key):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import gemm
    from repro.core import mp
    from repro.core.accuracy import max_rel_err
    from repro.launch.mesh import compat_make_mesh

    devices = jax.devices()
    if len(devices) < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, found {len(devices)}")
    mesh = compat_make_mesh((2, 2), ("rows", "cols"), devices=devices[:4])
    one = devices[0]
    a = random_tier(jax.random.fold_in(key, 5), (n, n), precision, dtype)
    b = random_tier(jax.random.fold_in(key, 6), (n, n), precision, dtype)

    plan1 = gemm.make_plan(n, n, n, dtype=dtype, precision=precision)
    c1, first1, warm1 = _timed(
        lambda x, y: gemm.matmul(x, y, plan=plan1),
        jax.device_put(a, one), jax.device_put(b, one))
    _assert_ran_as_planned(plan1)

    plan = gemm.make_plan(n, n, n, dtype=dtype, precision=precision,
                          mesh=mesh)
    blk = NamedSharding(mesh, P(plan.shard_axis, plan.shard_axis_n))
    a4, b4 = jax.device_put(a, blk), jax.device_put(b, blk)
    for v in (a4, b4):
        held = {s.device for l in mp.limbs(v) for s in l.addressable_shards}
        assert len(held) == 4, f"operand blocks sit on {held}"
    c4, first, warm = _timed(lambda x, y: gemm.matmul(x, y, plan=plan),
                             a4, b4)
    _assert_ran_as_planned(plan)
    shards = [s for s in mp.limbs(c4)[0].addressable_shards]
    held = {s.device for s in shards}
    assert len(held) == 4 and all(
        s.data.shape == (n // 2, n // 2) for s in shards), \
        f"C blocks: {[(s.device, s.data.shape) for s in shards]}"
    log(f"summa mesh=2x2 axes=({plan.shard_axis},{plan.shard_axis_n}) "
        f"k_panel={plan.k_panel or plan.bk} C blocks on "
        f"{sorted(d.id for d in held)}")

    bound = tier_bound(precision, dtype, n)
    rng = np.random.default_rng(SEED + n)
    rows, cols = rng.integers(0, n, 64).tolist(), rng.integers(0, n, 64)
    _report(f"one-chip gemm n={n}", check_product(
        a, b, c1, rows, cols.tolist(), dtype), bound, first1, warm1, plan1)
    _report(f"summa gemm n={n}", check_product(
        a, b, c4, rows, cols.tolist(), dtype), bound, first, warm, plan)
    agree = max_rel_err(jax.device_put(c4, one), c1)
    log(f"summa vs one chip: max rel diff {agree:.3e} (bound {bound:.3e})")
    if not agree <= bound:
        raise AssertionError("SUMMA product disagrees with one chip")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the SUMMA phase, on a 2x2 mesh")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.runtime import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0].platform == "
              f"{dev.platform!r})", file=sys.stderr)
        return 1
    OUT.mkdir(parents=True, exist_ok=True)
    plans = OUT / "gemm_plans.json"
    plans.unlink(missing_ok=True)  # no quarantine from an earlier run
    os.environ["REPRO_GEMM_CACHE"] = str(plans)
    cache_dir = compile_cache.enable()
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} "
        f"compile_cache={cache_dir}")

    from repro.core import efts
    from repro.runtime.faults import BackendFailoverWarning

    warnings.simplefilter("error", BackendFailoverWarning)
    warnings.filterwarnings("error", message="ozaki slicing infeasible",
                            category=RuntimeWarning)

    key = jax.random.PRNGKey(SEED)
    if args.chips == 4:
        # the SUMMA phase alone, over the limbs the plan layer takes on a
        # TPU (f32: the one-chip probe is why it refuses f64)
        summa_phase(N_SUMMA, "dd", np.dtype(np.float32), key)
    else:
        # a limb dtype that holds but is refused by make_plan (or the
        # reverse) fails a phase below: the plan layer's TPU refusals
        # must match this
        dtypes = probe(efts)
        if not dtypes:
            raise AssertionError("no limb dtype holds the EFT premise")
        for name in dtypes:
            for precision in ("dd", "td"):
                gemm_phase(N, precision, np.dtype(name), key)
        lu_phase(N, "dd", np.dtype(dtypes[0]), key, LU_BLOCK)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
