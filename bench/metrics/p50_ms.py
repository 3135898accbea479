"""50th percentile of per-query latency, due to answered, over every
query of the window (host clock)."""

import numpy as np


def read(ctx):
    lat = ctx.window.latency_s
    if lat is None or lat.size == 0:
        return None
    return float(np.percentile(lat, 50)) * 1e3
