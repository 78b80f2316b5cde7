"""Set-up: process start to the end of warm-up (imports, operands made on
the device, programs compiled or loaded from the cache, one warm call)."""


def read(ctx):
    return ctx.setup_s
