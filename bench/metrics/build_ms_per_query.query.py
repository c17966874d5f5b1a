"""build_ms_per_query.query: host build (trace resolution, job specs,
block placement, cell inputs) per query, from the benchmark's span around
``build_inputs`` in the traced window."""
from harness import layers


def read(ctx):
    seconds, n = layers.build_s(ctx), layers.per(ctx, "query")
    if seconds is None or not n:
        return None
    return seconds / n * 1e3
