"""Share of the traced window in which no operation ran on the device."""

import devtrace


def read(ctx):
    return devtrace.idle_share(ctx)
