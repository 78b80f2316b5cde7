"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (see benchmarks/common.py).
Mapping to the paper (also in DESIGN.md §7):

  bench_gemm       Fig. 2/5   GEMM GFlops vs n, per backend + TPU projection
  bench_tile       Fig. 3 + Tables II/III   BlockSpec (M_Tile) sweep
  bench_nonsquare  Fig. 4/6   tall-skinny shapes
  bench_accuracy   Eq. 6      E_L1 accuracy bands
  bench_lu         Fig. 8     blocked LU (Rgetrf) + block-size sweep
  bench_sdp        Tables IV/V   PDIPM time/iter + solution quality
  bench_lm         framework: LM train-step throughput + precision policy
"""

import inspect
import sys
import time


def _parse_argv(argv):
    """Split ``[module-filter] [--flag value | --flag=value ...]``.

    Flags become keyword options handed to any benchmark whose ``run()``
    accepts them (e.g. ``bench_gemm --mesh 2x2,1x4`` drives the SUMMA
    topology sweep); positional args filter which modules run.
    """
    only, opts = None, {}
    it = iter(argv)
    for arg in it:
        if arg.startswith("--"):
            key, eq, val = arg[2:].partition("=")
            if not eq:
                val = next(it, None)
                if val is None or val.startswith("--"):
                    # a valueless flag must fail here, not silently bind ""
                    # and run the (possibly minutes-long) default suite
                    raise SystemExit(
                        f"--{key} requires a value (use --{key}=VALUE)")
            opts[key.replace("-", "_")] = val
        elif only is None:
            only = arg
    return only, opts


# artifact each module contributes to: (path, row-name prefix).  On a
# module crash the sweep re-dumps this artifact so the rows (including the
# structured failure row) survive the crash and still upload from CI.
_ARTIFACTS = {
    "bench_gemm": ("BENCH_GEMM.json", "gemm_"),
    "bench_tile": ("BENCH_GEMM.json", "gemm_"),
    "bench_nonsquare": ("BENCH_GEMM.json", "gemm_"),
    "bench_lu": ("BENCH_LU.json", "lu_"),
}


def main() -> None:
    t0 = time.time()
    from repro.runtime import compile_cache

    compile_cache.enable()
    from . import (bench_accuracy, bench_gemm, bench_lm, bench_lu,
                   bench_nonsquare, bench_sdp, bench_tile)
    from . import common

    print("name,us_per_call,derived")
    only, opts = _parse_argv(sys.argv[1:])
    selected = [mod for mod in (bench_gemm, bench_tile, bench_nonsquare,
                                bench_accuracy, bench_lu, bench_sdp,
                                bench_lm)
                if not only or only in mod.__name__]
    accepted = {mod: {k for k in opts
                      if k in inspect.signature(mod.run).parameters}
                for mod in selected}
    unknown = opts.keys() - set().union(*accepted.values(), set())
    if unknown:
        # a misspelled flag must fail up front, not silently run the
        # (possibly minutes-long) default suite first
        raise SystemExit(
            f"unknown option(s) {sorted(unknown)}: no selected "
            f"benchmark's run() accepts them")
    failed = []
    for mod in selected:
        print(f"# {mod.__name__} — {mod.__doc__.strip().splitlines()[0]}",
              flush=True)
        short = mod.__name__.rsplit(".", 1)[-1]
        try:
            mod.run(**{k: opts[k] for k in accepted[mod]})
        # SystemExit passes through untouched: it is a *verdict* (the
        # bench-smoke conformance gate failing), not a crashed cell
        except Exception as e:  # noqa: BLE001 — sweep survival is the point
            failed.append(short)
            art = _ARTIFACTS.get(short)
            common.record_failure(
                ((art[1] if art else "") + f"error/{short}"), e)
            if art is not None:
                # re-dump so the rows emitted before the crash (plus the
                # failure row) reach the artifact the crash preempted
                common.dump_json(art[0], prefix=art[1])
    print(f"# total {time.time() - t0:.0f}s")
    if failed:
        # the artifacts (error rows included) are already written; the
        # nonzero exit makes the failure visible, and CI's upload steps
        # run on failure too (if: always())
        print(f"# FAILED: {', '.join(failed)} (see error rows)",
              flush=True)
        raise SystemExit(1)


if __name__ == '__main__':
    main()
