"""cells_per_s: cells answered over the whole closed-loop window, all cells
over all the elapsed time (host clock)."""


def read(ctx):
    if not ctx.answered or ctx.window_s <= 0:
        return None
    return ctx.cells() / ctx.window_s
