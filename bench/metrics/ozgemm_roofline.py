"""The ozgemm kernel's share of its roofline: the least time the chip
could take for the kernel's operations and bytes (``bench/counts``,
from the plan that ran) over the kernel's device time."""

import devtrace


def read(ctx):
    return devtrace.roofline_share(ctx, "ozgemm")
