"""Both entries at small shapes on the CPU: the program's answers pass
their limits, the control's fail them, and the result line has the
contract's shape."""

import json

import pytest

import run
from cells import SMALL, run_small


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_program_passes_and_control_fails(cell):
    r = run_small(cell, control=True)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert r["control_correct"] is False
    for name, c in r["checks"].items():
        assert c["value"] <= c["limit"]
        assert r["control_checks"][name]["value"] > c["limit"], name


def test_gemm_in_interpret_mode_on_the_pallas_kernel(monkeypatch):
    monkeypatch.setenv("REPRO_GEMM_BACKEND", "ozaki-pallas")
    r = run_small("gemm_td32.sq4096")
    assert r["_log"]["plan"]["backend"] == "ozaki-pallas"
    assert r["_log"]["plan"]["interpret"] is True
    assert r["correct"]


def test_same_seed_same_operands_and_wide_seeds():
    import numpy as np

    import operands

    spec = SMALL["lu_td32.n4096"]["traffic"]["operands"]
    one = operands.make(spec, "td", np.float32, 2 ** 40 + 3)
    two = operands.make(spec, "td", np.float32, 2 ** 40 + 3)
    other = operands.make(spec, "td", np.float32, 2 ** 40 + 4)
    for x, y, z in zip(one["a"], two["a"], other["a"]):
        assert (np.asarray(x) == np.asarray(y)).all()
        assert not (np.asarray(x) == np.asarray(z)).all()
        assert np.asarray(x).any()  # signal in every limb


def test_operand_limbs_do_not_overlap():
    """Each lower limb lies within half an ulp of the limb above it, as
    the program's arithmetic expects of its inputs."""
    import numpy as np

    import operands

    spec = [{"name": "a", "shape": [64, 64], "dist": "normal"}]
    ls = [np.asarray(l) for l in operands.make(spec, "td", np.float32,
                                               7)["a"]]
    for hi, lo in zip(ls, ls[1:]):
        assert (np.abs(lo) <= np.spacing(np.abs(hi)) / 2).all()
        assert (lo != 0).mean() > 0.99


def test_result_line_schema(capsys):
    r = run_small("gemm_td32.sq4096")
    run.emit(r)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    line = json.loads(last)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["metrics"]) == {"gemm_gflops", "gemm_p95_ms", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["checks"]["gemm_err"]) == {"value", "limit"}
