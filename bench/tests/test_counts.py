"""``bench/counts/ozgemm.py`` against counts made by hand."""

from types import SimpleNamespace

import numpy as np
import pytest

import devtrace

ozgemm = devtrace.kernel_counts("ozgemm")


def _plan(n_slices, full, nlimbs=3, blocks=(128, 128, 128)):
    bm, bn, bk = blocks
    return SimpleNamespace(bm=bm, bn=bn, bk=bk, n_slices=n_slices, full=full,
                           nlimbs=nlimbs, limb_dtype="float32")


def test_triangular_slice_set_td_4096():
    c = ozgemm.counts(_plan(14, False), 4096, 4096, 4096)
    # 14 slices: 1 + 2 + ... + 14 = 105 pairs, each 2 * 4096^3
    assert c["flops"] == 105 * 2 * 4096 ** 3
    # 3 f32 limbs = 12 bytes; A read once per column of C blocks (32),
    # B once per row (32), C written once
    assert c["bytes"] == 12 * (4096 * 4096 * 32 * 2 + 4096 * 4096)


def test_full_slice_set_and_epilogue():
    c = ozgemm.counts(_plan(10, True, nlimbs=2), 3584, 3584, 512,
                      epilogue="full")
    assert c["flops"] == 100 * 2 * 3584 * 3584 * 512
    a = 3584 * 512 * 28      # A: 28 columns of C blocks
    b = 512 * 3584 * 28
    assert c["bytes"] == 8 * (a + b + 2 * 3584 * 3584)  # C read and written


def test_counts_follow_the_plan_that_ran():
    from repro import gemm

    plan = gemm.make_plan(4096, 4096, 4096, dtype=np.float32,
                          precision="td", platform="tpu")
    assert (plan.backend, plan.n_slices, bool(plan.full)) == \
        ("ozaki-pallas", 14, False)
    base = ozgemm.counts(plan, 4096, 4096, 4096)["flops"]
    fewer = ozgemm.counts(plan.with_(n_slices=12), 4096, 4096, 4096)
    assert fewer["flops"] == base * 78 // 105
    assert ozgemm.counts(plan.with_(full=True), 4096, 4096, 4096)["flops"] \
        == base * 196 // 105


@pytest.mark.parametrize("s,pairs", [(1, 1), (2, 3), (10, 55), (14, 105)])
def test_slice_pairs(s, pairs):
    assert ozgemm.slice_pairs(s, False) == pairs
    assert ozgemm.slice_pairs(s, True) == s * s
