"""Device time per solve in the LU's sequential loops: the ``_rgetrf2``
panel programs and the ``_trsm`` substitution programs."""

import devtrace

PROGRAMS = r"_rgetrf2|_trsm"


def read(ctx):
    return devtrace.program_ms_per_call(ctx, PROGRAMS)
