"""The reduction of traces recorded on a TPU v5e (``data/``) into the
per-layer metrics.

``gemm_td32_256``: three calls of a td GEMM at n = 256; ``lu_dd32_64``:
three dd LU solves at n = 64, block 32; both recorded with the harness's
profiler options.  From the LU trace the ops nested inside loops (which
``devtrace.outermost`` drops on loading) and the ``/host:metadata`` plane
were removed before it was kept, to keep it under 1 MB.
"""

from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import devtrace
import run

DATA = Path(__file__).resolve().parent / "data"
SPAN = run.WINDOW_SPAN


@pytest.fixture(scope="module")
def gemm_trace():
    return devtrace.load(DATA / "gemm_td32_256.xplane.pb")


@pytest.fixture(scope="module")
def lu_trace():
    return devtrace.load(DATA / "lu_dd32_64.xplane.pb")


def _ops(tr):
    return [e for evs in tr.ops.values() for e in evs]


def _ctx(tr, cell):
    peaks = run.load_json(run.BENCH / "peaks.json")["devices"]["TPU v5 lite"]
    return run.Context(cell, {"start": 0.0, "end": 1.0,
                              "calls": [(0.0, 0.001, 0.002)] * 3},
                       1.0, trace=tr, peaks=peaks)


@pytest.mark.parametrize("name", ["gemm_trace", "lu_trace"])
def test_device_programs_start_after_the_host_launched_them(name, request):
    tr = request.getfixturevalue(name)
    starts = sorted(m[1] for evs in tr.modules.values() for m in evs)
    launches = sorted(h[1] for h in tr.host
                      if h[0] == "tpu::System::Execute")
    assert len(starts) == len(launches) > 0
    assert all(s >= l for s, l in zip(starts, launches))


def test_window_leaves_out_the_first_call(gemm_trace):
    spans = gemm_trace.spans(SPAN)
    assert len(spans) == 3 and gemm_trace.calls(SPAN) == 2
    assert gemm_trace.window(SPAN) == (spans[1][1], spans[2][2])


def test_gemm_kernel_matching_and_roofline(gemm_trace):
    win = gemm_trace.window(SPAN)
    hits = devtrace.clip(devtrace.matching(_ops(gemm_trace), r"ozgemm"), win)
    assert len(hits) == 2  # one kernel launch per call in the window
    assert all(h[0].startswith("jit__execute_2d_jit/") for h in hits)
    from repro import gemm

    plan = gemm.make_plan(256, 256, 256, dtype=np.float32, precision="td",
                          platform="tpu")
    cell = SimpleNamespace(plan=plan, kernel_calls=lambda: [
        ("ozgemm", {"m": 256, "n": 256, "k": 256, "epilogue": "none"})])
    ctx = _ctx(gemm_trace, cell)
    got = run.read_metrics(run.metric_entries(run.registry(),
                                              "gemm_td32.sq4096", True), ctx)
    assert 0 < got["ozgemm_roofline.gemm"]["value"] <= 100
    assert got["ozgemm_ms.gemm"]["value"] == pytest.approx(
        sum(e - s for _, s, e in hits) * 1e-6 / 2)
    assert 0 <= got["idle_share.gemm"]["value"] < 100
    assert "bound by operations" in ctx.notes[0]


def test_lu_programs_and_gap_attribution(lu_trace):
    ctx = _ctx(lu_trace, SimpleNamespace())
    got = run.read_metrics(run.metric_entries(run.registry(),
                                              "lu_td32.n4096", True), ctx)
    assert got["lu_loops_ms"]["value"] > 0 and got["ozgemm_ms.lu"]["value"] > 0
    red = run.trace_reduction(ctx)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["busy_s"] / red["window_s"] == pytest.approx(
        1 - got["idle_share.lu"]["value"] / 100)
    b = red["breakdown"]
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    names = {h[0] for h in lu_trace.host} | {"idle"}
    assert all(n in names for n, _ in b["idle_gaps"])
    secs = [s for _, s in b["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)
