"""95th percentile (nearest rank) of the time from issue to
``block_until_ready``, over every call of the window."""

import math


def read(ctx):
    lat = sorted(ctx.latencies)
    return lat[math.ceil(0.95 * len(lat)) - 1] * 1e3
