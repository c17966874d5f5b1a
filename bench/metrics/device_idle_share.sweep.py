"""device_idle_share.sweep: 1 - (union of device-op intervals) / traced
window, from the device trace."""
from harness import layers


def read(ctx):
    return layers.idle_share(ctx)
