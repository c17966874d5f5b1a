"""engine_dispatch_ms_per_query.query: the program's ``pack`` (kernel inputs
padded and stacked) and ``dispatch`` (the executable's call, host-to-device
copies included) spans per query, in ms."""
from harness import layers, spans

spans.install()


def read(ctx):
    seconds = spans.seconds(ctx, spans.PACK, spans.DISPATCH)
    n = layers.per(ctx, "query")
    if seconds is None or not n:
        return None
    return seconds / n * 1e3
