"""engine_records_ms_per_cell.sweep: the program's ``unpack`` (results out
of the kernel's arrays) and ``records`` (``RunRecord``s and their cache
files) spans per cell, in ms."""
from harness import layers, spans

spans.install()


def read(ctx):
    seconds = spans.seconds(ctx, spans.UNPACK, spans.RECORDS)
    n = layers.per(ctx, "cell")
    if seconds is None or not n:
        return None
    return seconds / n * 1e3
