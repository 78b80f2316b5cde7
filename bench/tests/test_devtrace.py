"""The trace reduction: union of intervals, gaps, kernel matching and the
attribution of idle gaps to what the host was doing."""

import pytest

import devtrace


def test_union_merges_overlaps_and_touching():
    ev = [("a", 10, 20), ("b", 15, 30), ("c", 30, 35), ("d", 50, 60)]
    assert devtrace.union(ev) == [(10, 35), (50, 60)]


def test_busy_and_gaps_inside_a_window():
    ev = [("a", 0, 20), ("b", 40, 70), ("c", 90, 200)]
    win = (10, 100)
    assert devtrace.busy_ns(ev, win) == 10 + 30 + 10
    assert devtrace.gaps(ev, win) == [(20, 40), (70, 90)]
    assert devtrace.gaps([], win) == [(10, 100)]


def test_matching_and_seconds_by_name():
    ev = [("ozgemm_kernel", 0, 1000), ("fusion.3", 1000, 1500),
          ("ozgemm_kernel", 2000, 3000)]
    hits = devtrace.matching(ev, r"ozgemm")
    assert len(hits) == 2
    assert devtrace.seconds_by_name(hits, (0, 2500)) == {
        "ozgemm_kernel": pytest.approx(1.5e-6)}


def test_gaps_are_named_by_the_innermost_host_event():
    ops = [("k", 0, 100), ("k", 300, 400)]
    host = [("bench.call", 0, 400), ("PjitFunction(concatenate)", 150, 250)]
    b = devtrace.breakdown(ops, host, (0, 500))
    assert b["device_ops"] == [["k", pytest.approx(2e-7)]]
    assert b["idle_gaps"][0] == ["PjitFunction(concatenate)",
                                 pytest.approx(2e-7)]
    assert b["idle_gaps"][1] == ["idle", pytest.approx(1e-7)]


def test_breakdown_keeps_ten_of_each():
    ops = [(f"op{i}", 100 * i, 100 * i + 50) for i in range(30)]
    b = devtrace.breakdown(ops, [], (0, 3000))
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
