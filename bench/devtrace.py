"""Reduce a profiler trace to what the per-layer metrics read.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
A TPU shows up as a plane named ``/device:TPU:<i>``; its ``XLA Ops`` line
holds one event per operation that ran (inside a loop, one per
iteration) and its ``XLA Modules`` line one event per program (jit) that
ran.  The host's plane holds the benchmark's own spans
(``jax.profiler.TraceAnnotation``) and JAX's dispatch events.  The
device's times are shifted onto the host's clock (``align``).

Everything here works on plain ``(name, start_ns, end_ns)`` tuples, and
is tested on small traces recorded on the chip and kept in
``bench/tests/data``.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


@dataclass
class Trace:
    """Device ops and programs per chip, and host events, in ns on the
    host's clock."""

    ops: dict = field(default_factory=dict)       # plane -> [(name, s, e)]
    modules: dict = field(default_factory=dict)   # plane -> [(name, s, e)]
    host: list = field(default_factory=list)      # [(name, s, e)]
    shift_ns: float = 0.0  # added to the device's times (see ``align``)

    def spans(self, name: str) -> list:
        """The benchmark's host spans of that name, in order."""
        return sorted((h for h in self.host if h[0] == name),
                      key=lambda h: h[1])

    def window(self, span: str) -> tuple:
        """(start, end) of the traced window: from the second host span
        named ``span`` to the end of the last, so that the first call
        after the profiler starts, which pays its set-up, is left out."""
        hits = self.spans(span)
        if not hits:
            raise ValueError(f"no host span {span!r} in the trace")
        return hits[min(1, len(hits) - 1)][1], hits[-1][2]

    def calls(self, span: str) -> int:
        """How many calls the window holds."""
        return max(len(self.spans(span)) - 1, 1)


def find_xplane(log_dir) -> str:
    paths = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def _program(name: str) -> str:
    """``jit__trsm(1281...)`` -> ``jit__trsm``."""
    return re.sub(r"\(\d+\)$", "", name)


def _named_ops(ops, modules) -> list:
    """Ops renamed ``<program>/<op>``: the program whose run holds the op,
    and the op's HLO name without its shape text."""
    mods = sorted(modules, key=lambda m: m[1])
    out, i = [], 0
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        while i + 1 < len(mods) and mods[i + 1][1] <= s:
            i += 1
        prog = _program(mods[i][0]) if mods and mods[i][1] <= s < mods[i][2] \
            else "?"
        out.append((f"{prog}/{name.split(' = ', 1)[0]}", s, e))
    return out


def align(modules, host) -> float:
    """The shift that puts the device's times on the host's clock.

    The two clocks of a trace can differ by milliseconds.  The k-th
    program run on the device is the k-th ``tpu::System::Execute`` on the
    host, and no program starts before the host launched it; the shift is
    the least that makes that true for every program.  0 where the counts
    do not pair up."""
    launches = sorted(h[1] for h in host if h[0] == "tpu::System::Execute")
    starts = sorted(m[1] for m in modules)
    if not starts or len(launches) != len(starts):
        return 0.0
    return float(max(l - s for l, s in zip(launches, starts)))


def outermost(events) -> list:
    """(name, start, end) of the events that no earlier event of the line
    encloses: a loop's op, not each op of each of its iterations (a loop
    of the LU runs millions).  Every op on the device is inside one of
    them, so their union is the device's busy time."""
    out, end = [], -1.0
    for e in events:
        s = e.start_ns
        e_end = s + e.duration_ns
        if e_end <= end:
            continue
        out.append((e.name, s, e_end))
        end = max(end, e_end)
    return out


def load(path) -> Trace:
    """Read an ``.xplane.pb`` file (or the one under a trace directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(str(path))
    tr = Trace()
    ops, modules = {}, {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dest is not None:
                    dest[plane.name] = outermost(line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                tr.host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.duration_ns > 0]
    tr.shift_ns = align([m for ms in modules.values() for m in ms], tr.host)

    def shifted(evs):
        return [(n, s + tr.shift_ns, e + tr.shift_ns) for n, s, e in evs]

    tr.modules = {p: shifted(ms) for p, ms in modules.items()}
    tr.ops = {p: shifted(_named_ops(os_, modules.get(p, [])))
              for p, os_ in ops.items()}
    return tr


def clip(events, window) -> list:
    """Events cut to the window; events outside it dropped."""
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(events) -> list:
    """Disjoint, sorted (start, end) intervals covering the events."""
    out = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def busy_ns(events, window) -> float:
    """Length of the union of the events inside the window."""
    return float(sum(e - s for s, e in union(clip(events, window))))


def gaps(events, window) -> list:
    """Idle (start, end) intervals of the window: no event running."""
    lo, hi = window
    out, t = [], lo
    for s, e in union(clip(events, window)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def covering(host, t: float) -> str:
    """The innermost host event running at time t, or ``idle`` where the
    host ran none."""
    best = None
    for name, s, e in host:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "idle"


def matching(events, pattern) -> list:
    """Events whose name matches a regular expression."""
    rx = re.compile(pattern)
    return [ev for ev in events if rx.search(ev[0])]


def seconds_by_name(events, window) -> dict:
    """Total device seconds per event name inside the window."""
    out = defaultdict(float)
    for name, s, e in clip(events, window):
        out[name] += (e - s) * 1e-9
    return dict(out)


def breakdown(ops, host, window, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps named by what the host was doing in each."""
    by_op = sorted(seconds_by_name(ops, window).items(), key=lambda kv: -kv[1])
    idle = sorted(gaps(ops, window), key=lambda g: g[0] - g[1])
    return {
        "device_ops": [[n, s] for n, s in by_op[:top]],
        "idle_gaps": [[covering(host, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in idle[:top]],
    }


# ---------------------------------------------------------------------------
# the readings the per-layer metrics share
# ---------------------------------------------------------------------------


def kernel_counts(kernel: str):
    """``bench/counts/<kernel>.py``: the kernel's trace-event pattern and
    its operation and byte counts."""
    import importlib.util

    path = os.path.join(os.path.dirname(__file__), "counts", f"{kernel}.py")
    spec = importlib.util.spec_from_file_location(f"counts_{kernel}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernel_seconds(ctx, kernel: str) -> float:
    """Device seconds in the kernel's events over the traced window."""
    ev = matching(ctx.device_ops(), kernel_counts(kernel).EVENT)
    return sum(seconds_by_name(ev, ctx.device_window()).values())


def kernel_ms_per_call(ctx, kernel: str):
    s = kernel_seconds(ctx, kernel)
    return s * 1e3 / ctx.traced_calls if s > 0 else None


def program_ms_per_call(ctx, pattern: str):
    """Device time per call in the programs (jit modules) whose names
    match, from the modules line of every chip."""
    ev = matching([e for evs in ctx.trace.modules.values() for e in evs],
                  pattern)
    s = sum(seconds_by_name(ev, ctx.device_window()).values())
    return s * 1e3 / ctx.traced_calls if s > 0 else None


def idle_share(ctx) -> float:
    """Per cent of the traced window with no op on the device (averaged
    over the chips that ran ops)."""
    win = ctx.device_window()
    planes = [evs for evs in ctx.trace.ops.values() if evs]
    busy = sum(busy_ns(evs, win) for evs in planes) / len(planes)
    return 100.0 * (1.0 - busy / (win[1] - win[0]))


def roofline_share(ctx, kernel: str):
    """Per cent of the kernel's roofline: the larger of its operations
    over peak FLOP/s and its bytes over peak bytes/s, over its time."""
    s = kernel_seconds(ctx, kernel)
    if s <= 0:
        return None
    mod = kernel_counts(kernel)
    flops = nbytes = 0
    for name, shape in ctx.cell.kernel_calls():
        if name == kernel:
            c = mod.counts(ctx.cell.plan, **shape)
            flops, nbytes = flops + c["flops"], nbytes + c["bytes"]
    calls = ctx.traced_calls
    t_ops = calls * flops / ctx.peaks["bf16_flops_per_s"]
    t_bytes = calls * nbytes / ctx.peaks["hbm_bytes_per_s"]
    ctx.note(f"{kernel} roofline: bound by "
             f"{'operations' if t_ops >= t_bytes else 'bytes'} "
             f"(ops {t_ops:.6f} s, bytes {t_bytes:.6f} s, "
             f"kernel {s:.6f} s over {calls} calls)")
    return 100.0 * max(t_ops, t_bytes) / s
