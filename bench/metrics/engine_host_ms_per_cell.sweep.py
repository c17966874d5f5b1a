"""engine_host_ms_per_cell.sweep: the engine's host side (packing,
dispatch, unpacking, record writes) per cell: request spans less build
spans less the device-busy time inside ``run_batch`` spans."""
from harness import layers


def read(ctx):
    seconds, n = layers.engine_host_s(ctx), layers.per(ctx, "cell")
    if seconds is None or not n:
        return None
    return seconds / n * 1e3
