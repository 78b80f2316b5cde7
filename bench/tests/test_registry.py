"""The harness finds everything by name, and refuses what it cannot
measure."""

import json
import re
import shutil
from types import SimpleNamespace

import pytest

import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def reg():
    return run.registry()


def test_every_cell_resolves_to_its_files(reg):
    for name, w in reg["workloads"].items():
        _, cfg, traffic = run.cell_files(reg, name)
        assert cfg["name"] == w["config"]
        assert (run.BENCH / "entries" / f"{cfg['entry']}.py").is_file()
        assert set(cfg["limits"]) and traffic["clients"] == 1


def test_every_metric_has_a_reader_and_every_cell_reports(reg):
    bench = reg["bench"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert run.reader_path(m["name"]).is_file(), m
    for name in reg["workloads"]:
        e2e = {m["name"] for m in run.metric_entries(reg, name, False)}
        layer = run.metric_entries(reg, name, True)
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
        assert {m["moves"] for m in layer} <= e2e


def test_benchmark_json_keeps_the_contract_shape(reg):
    bench = reg["bench"]
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(reg["workloads"])


def test_a_cell_and_a_metric_are_added_by_adding_files(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(run.BENCH, root / "bench")
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "traffic" / "sq256.json").write_text(json.dumps(
        {**json.loads((run.BENCH / "traffic" / "sq4096.json").read_text()),
         "operands": [{"name": "a", "shape": [256, 256], "dist": "normal"},
                      {"name": "b", "shape": [256, 256], "dist": "normal"}]}))
    (root / "bench" / "metrics" / "calls.gemm.py").write_text(
        "def read(ctx):\n    return len(ctx.run['calls'])\n")
    bench["workloads"].append({"name": "gemm_td32.sq256", "config":
                               "gemm_td32", "traffic": "sq256", "chips": 1,
                               "why": "small"})
    bench["per_layer"].append({"name": "calls.gemm", "unit": "calls",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine host path",
                               "moves": "gemm_gflops",
                               "workloads": ["gemm_td32.sq256"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    reg = run.registry(root)
    w, cfg, traffic = run.cell_files(reg, "gemm_td32.sq256", root)
    assert cfg["entry"] == "gemm" and traffic["operands"][0]["shape"] == [256,
                                                                          256]
    entries = run.metric_entries(reg, "gemm_td32.sq256", True)
    assert [m["name"] for m in entries] == ["calls.gemm"]
    ctx = SimpleNamespace(run={"calls": [0, 1, 2]})
    assert run.read_metrics(entries, ctx, root) == {
        "calls.gemm": {"value": 3.0, "unit": "calls"}}


def test_metrics_split_by_cell_share_their_base_reader(tmp_path):
    metrics = tmp_path / "bench" / "metrics"
    metrics.mkdir(parents=True)
    for name in ("idle_share", "dispatch_ms.lu"):
        (metrics / f"{name}.py").write_text("")
    assert run.reader_path("idle_share.gemm", tmp_path).name == \
        "idle_share.py"
    assert run.reader_path("dispatch_ms.lu", tmp_path).name == \
        "dispatch_ms.lu.py"


def test_unknown_cell_is_refused(reg):
    with pytest.raises(run.Refused):
        run.cell_files(reg, "no_such.cell")


def test_no_tpu_exits_nonzero_with_no_result(capsys):
    rc = run.main(["--workload", "gemm_td32.sq4096", "--seed", "1",
                   "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "no TPU" in out.err


@pytest.mark.parametrize("kind,count,chips,ok", [
    ("TPU v5 lite", 1, 1, True), ("TPU v9", 1, 1, False),
    ("TPU v5 lite", 1, 4, False), ("TPU v5 lite", 4, 4, True)])
def test_device_kind_and_chip_count(monkeypatch, kind, count, chips, ok):
    import jax

    peaks = run.load_json(run.BENCH / "peaks.json")["devices"]
    dev = SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda: [dev] * count)
    if ok:
        assert run.require_chip(chips, peaks) == {
            "platform": "tpu", "kind": kind, "count": count}
    else:
        with pytest.raises(run.Refused):
            run.require_chip(chips, peaks)


def test_only_the_benchmark_files_make_no_result(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ cannot run."""
    import subprocess
    import sys

    root = tmp_path / "bare"
    shutil.copytree(run.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests"))
    shutil.copy(run.ROOT / "BENCHMARK.json", root)
    code = ("import sys; sys.path.insert(0, 'bench'); import run; "
            "run.run(run.parse(['--workload', 'gemm_td32.sq4096', '--seed', "
            "'1', '--seconds', '1']), check_chip=False)")
    p = subprocess.run([sys.executable, "-c", code], cwd=root,
                       capture_output=True, text=True, timeout=120,
                       env={"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout == ""
    assert "No module named 'repro'" in p.stderr
