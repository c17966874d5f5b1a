"""setup_s: seconds from the start of the run to the window's opening
(imports, the chip, the compile cache, the warm-up request)."""


def read(ctx):
    return ctx.setup_s
