"""A run with the timed path broken underneath comes out not correct,
once for each fault a cell can have (one chip: no exchange to drop)."""

import pytest

import faults
from cells import SMALL, run_small


@pytest.mark.parametrize("fault", faults.FAULTS)
@pytest.mark.parametrize("cell", sorted(SMALL))
def test_fault_is_not_correct(cell, fault):
    entry = {"gemm_td32.sq4096": "gemm", "lu_td32.n4096": "lu"}[cell]
    with faults.planted(entry, fault):
        r = run_small(cell)
    assert not r["correct"] and r["failed"] == 1
