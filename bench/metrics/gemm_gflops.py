"""2mnk (the paper's Eq. 4) times calls completed, over the window."""


def read(ctx):
    flops = getattr(ctx.cell, "flops", None)
    if flops is None:
        return None
    return flops * len(ctx.run["calls"]) / ctx.window_s / 1e9
