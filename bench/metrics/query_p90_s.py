"""query_p90_s: 90th percentile of seconds from sending a query to its
records, over every query of the window (host clock)."""
import numpy as np


def read(ctx):
    lat = [s.latency for s in ctx.served]
    return float(np.percentile(lat, 90)) if lat else None
