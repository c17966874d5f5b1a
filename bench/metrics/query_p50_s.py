"""query_p50_s: median seconds from sending a query to its records,
over every query of the window (host clock)."""
import numpy as np


def read(ctx):
    lat = [s.latency for s in ctx.served]
    return float(np.percentile(lat, 50)) if lat else None
