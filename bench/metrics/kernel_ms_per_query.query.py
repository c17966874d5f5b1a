"""kernel_ms_per_query.query: device time of the surrogate executable per
query, in ms."""
from harness import layers


def read(ctx):
    seconds, n = layers.kernel_s(ctx), layers.per(ctx, "query")
    if seconds is None or not n:
        return None
    return seconds / n * 1e3
