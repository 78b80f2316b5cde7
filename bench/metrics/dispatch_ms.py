"""Median host time from entering the cell's call (for a GEMM:
``gemm.make_plan`` and ``gemm.execute``) to its return, before the device
finishes: planning, padding, dispatch."""

import statistics


def read(ctx):
    return statistics.median(ctx.dispatches) * 1e3
