"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: its
configuration file, its traffic file ``bench/traffic/<traffic>.json``, the
entry that drives the program ``bench/entries/<entry>.py`` and one reader
per metric ``bench/metrics/<metric>.py`` (see ``reader_path``).  A run sets up (operands on the
device from the seed, the program warmed on every shape the cell uses),
drives one caller in a closed loop for ``--seconds``, checks a seeded
sample of the answers against exact references and prints, as the last
line of standard output, one JSON object.  ``--trace 1`` records a
profiler trace of a shorter window instead and reports the per-layer
metrics read from it.  Without a TPU whose ``device_kind`` is in
``bench/peaks.json`` the run exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import warnings  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
BUILD = ROOT / "build" / "bench"  # plan cache and traces; gitignored
WINDOW_SPAN = "bench.call"


class Refused(Exception):
    """The cell cannot be measured here (no chip, unknown device or cell)."""


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file of the benchmark by path (metric files carry dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# registry: everything by name from BENCHMARK.json
# ---------------------------------------------------------------------------


def registry(root: Path = ROOT) -> dict:
    bench = load_json(root / "BENCHMARK.json")
    return {
        "bench": bench,
        "configs": {c["name"]: c for c in bench["configs"]},
        "workloads": {w["name"]: w for w in bench["workloads"]},
    }


def cell_files(reg: dict, name: str, root: Path = ROOT):
    """(workload entry, configuration, traffic) of a cell."""
    w = reg["workloads"].get(name)
    if w is None:
        raise Refused(f"unknown workload {name!r}; BENCHMARK.json has "
                      f"{sorted(reg['workloads'])}")
    cfg = load_json(root / reg["configs"][w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return w, cfg, traffic


def metric_entries(reg: dict, name: str, trace: bool) -> list:
    """The metrics a cell reports: end-to-end ones untraced, per-layer
    ones traced; a metric with a ``workloads`` list only in those cells."""
    group = reg["bench"]["per_layer" if trace else "end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


def reader_path(name: str, root: Path = ROOT) -> Path:
    """A metric's reader: ``bench/metrics/<name>.py``, or for a name split
    by cell (``idle_share.lu``) the reader its base shares,
    ``bench/metrics/<base>.py``."""
    metrics = root / "bench" / "metrics"
    own = metrics / f"{name}.py"
    return own if own.is_file() else metrics / f"{name.split('.')[0]}.py"


def read_metrics(entries, ctx, root: Path = ROOT) -> dict:
    """Each metric's reader (``reader_path``); a reader that finds nothing
    to read returns None and the metric is left out."""
    out = {}
    for m in entries:
        value = load_module(reader_path(m["name"], root)).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def require_chip(chips: int, peaks: dict) -> dict:
    """The device the cell runs on; Refused unless it is a known TPU."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise Refused(f"no TPU: jax.devices()[0].platform is "
                      f"{dev.platform!r}")
    if dev.device_kind not in peaks:
        raise Refused(f"device_kind {dev.device_kind!r} is not in "
                      f"bench/peaks.json ({sorted(peaks)})")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, found {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------


class Reservoir:
    """A seeded uniform sample of the window's answers (reservoir
    sampling): the answers checked after the window closes."""

    def __init__(self, size: int, rng):
        self.size, self.rng, self.kept = size, rng, []

    def __call__(self, i: int, out) -> None:
        if len(self.kept) < self.size:
            self.kept.append((i, out))
        else:
            j = int(self.rng.integers(0, i + 1))
            if j < self.size:
                self.kept[j] = (i, out)


def window(call, seconds: float, keep) -> dict:
    """One caller in a closed loop until ``seconds`` have passed: each
    call is timed from issue to ``block_until_ready``."""
    import jax

    calls = []
    start = time.perf_counter()
    deadline = start + seconds
    while time.perf_counter() < deadline:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            t0 = time.perf_counter()
            out = call()
            t1 = time.perf_counter()
            jax.block_until_ready(out)
            t2 = time.perf_counter()
        keep(len(calls), out)
        calls.append((t0, t1, t2))
    return {"start": start, "end": calls[-1][2], "calls": calls}


class CompileCounter:
    """Programs compiled while a phase is on, by name, and the seconds
    they took: JAX's backend-compile monitoring event (a program loaded
    from the persistent cache does not fire it)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.phase, self.names, self.seconds = None, {}, {}
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, fun_name="?", **_kw):
        if self.phase and name == self.EVENT:
            names = self.names.setdefault(self.phase, {})
            names[fun_name] = names.get(fun_name, 0) + 1
            self.seconds[self.phase] = self.seconds.get(self.phase, 0) + secs

    def summary(self, phase: str) -> dict:
        return {"programs": sum(self.names.get(phase, {}).values()),
                "seconds": self.seconds.get(phase, 0.0),
                "by_name": self.names.get(phase, {})}


def configure_program() -> str:
    """A fresh plan cache (no tuned blocks, no quarantine), failover as an
    error, and JAX's persistent compilation cache as the program sets it."""
    BUILD.mkdir(parents=True, exist_ok=True)
    plans = BUILD / "gemm_plans.json"
    plans.unlink(missing_ok=True)
    os.environ["REPRO_GEMM_CACHE"] = str(plans)
    from repro.runtime import compile_cache
    from repro.runtime.faults import BackendFailoverWarning

    warnings.simplefilter("error", BackendFailoverWarning)
    warnings.filterwarnings("error", message="ozaki slicing infeasible",
                            category=RuntimeWarning)
    return compile_cache.enable()


def ran_as_planned(plan) -> None:
    """The default plan ran: no tuned blocks, no backend quarantined."""
    from repro.gemm import cache as plan_cache

    q = plan_cache.quarantined(plan.platform, plan.backend, plan.nlimbs)
    if plan.source != "heuristic" or q is not None:
        raise RuntimeError(f"the plan that ran is not the default one "
                           f"(source={plan.source}, quarantine={q})")


def memory_peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


class Context:
    """What a metric reader sees: the cell, the window, and the trace."""

    def __init__(self, cell, run, setup_s, trace=None, peaks=None):
        self.cell, self.run, self.setup_s = cell, run, setup_s
        self.trace, self.peaks = trace, peaks
        self.notes = []

    @property
    def latencies(self) -> list:
        return [t2 - t0 for t0, _, t2 in self.run["calls"]]

    @property
    def dispatches(self) -> list:
        return [t1 - t0 for t0, t1, _ in self.run["calls"]]

    @property
    def window_s(self) -> float:
        return self.run["end"] - self.run["start"]

    def device_window(self) -> tuple:
        """The traced window on the trace's clock (ns)."""
        return self.trace.window(WINDOW_SPAN)

    @property
    def traced_calls(self) -> int:
        return self.trace.calls(WINDOW_SPAN)

    def device_ops(self) -> list:
        """Every op on every chip the cell used (the readers clip them to
        the window)."""
        return [ev for evs in self.trace.ops.values() for ev in evs]

    def note(self, text: str) -> None:
        self.notes.append(text)


def trace_reduction(ctx) -> dict:
    """busy_s / window_s (averaged over the chips that ran ops) and the
    breakdown for the result line."""
    import devtrace

    win = ctx.device_window()
    window_s = (win[1] - win[0]) * 1e-9
    busy_s = (1.0 - devtrace.idle_share(ctx) / 100.0) * window_s
    return {"busy_s": busy_s, "window_s": window_s,
            "breakdown": devtrace.breakdown(ctx.device_ops(), ctx.trace.host,
                                            win)}


# ---------------------------------------------------------------------------


def run(args, root: Path = ROOT, check_chip: bool = True,
        overrides: dict | None = None, control: bool = False) -> dict:
    """One run of a cell; returns the result line's object.

    The tests and ``calibrate.py`` call this directly: ``check_chip=False``
    skips the look for a chip, ``overrides`` replaces keys of the
    configuration and the traffic (``{"config": {...}, "traffic": {...}}``)
    for small sizes on the CPU, and ``control=True`` also checks the
    control (``Cell.control``) against the same limits, under
    ``control_checks`` and ``control_correct``.
    """
    reg = registry(root)
    w, cfg, traffic = cell_files(reg, args.workload, root)
    overrides = overrides or {}
    cfg = {**cfg, **overrides.get("config", {})}
    traffic = {**traffic, **overrides.get("traffic", {})}
    peaks = load_json(root / "bench" / "peaks.json")["devices"]
    sys.path.insert(0, str(root / "src"))
    sys.path.insert(0, str(root / "bench"))
    import jax
    import numpy as np

    device = require_chip(w["chips"], peaks) if check_chip else {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind, "count": len(jax.devices())}
    configure_program()
    counter = CompileCounter()
    counter.phase = "setup"
    import operands

    if traffic.get("loop") != "closed" or traffic.get("clients") != 1:
        raise Refused("the harness drives one caller in a closed loop")
    ops = operands.make(traffic["operands"], cfg["precision"],
                        np.dtype(cfg["limb_dtype"]), args.seed)
    entry = load_module(root / "bench" / "entries" / f"{cfg['entry']}.py")
    cell = entry.Cell(cfg, traffic, ops)
    jax.block_until_ready(cell.call())  # warm-up: every shape of the cell
    counter.phase = None
    setup_s = time.perf_counter() - T_START

    seconds = args.seconds
    trace_dir = BUILD / f"trace-{args.workload}"
    if args.trace:
        seconds = min(seconds, traffic["trace_seconds"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        # the Python tracer would slow the host path it measures
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    keep = Reservoir(traffic["checked_calls"], operands.host_rng(args.seed, 1))
    counter.phase = "window"
    try:
        record = window(cell.call, seconds, keep)
    finally:
        counter.phase = None
        if args.trace:
            jax.profiler.stop_trace()
    device["memory_peak_bytes"] = memory_peak_bytes()
    ran_as_planned(cell.plan)

    ctx = Context(cell, record, setup_s, peaks=peaks[device["kind"]]
                  if device["kind"] in peaks else None)
    extra = {}
    if args.trace:
        import devtrace

        ctx.trace = devtrace.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        extra = trace_reduction(ctx)
        device["busy_s"], device["window_s"] = extra["busy_s"], \
            extra["window_s"]
    metrics = read_metrics(metric_entries(reg, args.workload, args.trace),
                           ctx, root)

    answers = [out for _, out in keep.kept]
    checks, failed, correct = compare(
        cell.check(answers, operands.host_rng(args.seed, 2)), cfg["limits"])
    result = {
        "correct": correct,
        "attempted": len(record["calls"]),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if args.trace:
        result["breakdown"] = extra["breakdown"]
    if control:
        result["control_checks"], _, result["control_correct"] = compare(
            cell.check([cell.control()], operands.host_rng(args.seed, 3)),
            cfg["limits"])
    result["checks"] = checks
    result["_log"] = {
        "plan": cell.describe(), "calls": len(record["calls"]),
        "window_s": ctx.window_s, "checked_answers": len(answers),
        "latency_s": latency_summary(ctx.latencies),
        "compiles_in_setup": counter.summary("setup"),
        "compiles_in_window": counter.summary("window"), "notes": ctx.notes,
    }
    return result


def compare(numbers: dict, limits: dict) -> tuple:
    """Each number compared beside its limit, how many exceed theirs, and
    whether the answers are correct (every limit met, none missing)."""
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    failed = sum(1 for c in checks.values() if not c["value"] <= c["limit"])
    return checks, failed, failed == 0 and set(numbers) == set(limits)


def latency_summary(lat: list) -> dict:
    """Median, extremes and the slowest calls' positions, for the log."""
    order = sorted(range(len(lat)), key=lambda i: -lat[i])
    return {"median": statistics.median(lat), "min": min(lat),
            "max": max(lat), "slowest": [(i, lat[i]) for i in order[:3]]}


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def emit(result: dict) -> None:
    """Log lines, then the numbers compared as the last lines of standard
    error, then the result as the last line of standard output."""
    for k, v in result.pop("_log").items():
        print(f"{k}: {v}", flush=True)
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr)
        return 2
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
