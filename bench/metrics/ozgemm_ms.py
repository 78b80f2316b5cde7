"""Device time per call of the window (a GEMM, or a whole solve) in the
ozgemm kernel's events."""

import devtrace


def read(ctx):
    return devtrace.kernel_ms_per_call(ctx, "ozgemm")
