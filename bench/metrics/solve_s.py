"""Time to solution: the window up to the last completed solve, over the
solves completed."""


def read(ctx):
    return ctx.window_s / len(ctx.run["calls"])
