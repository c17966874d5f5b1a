"""kernel_us_per_step_run.query: device time of the surrogate executable
over the steps its sub-batches ran (each sub-batch's slowest lane, from
the program's ``unpack`` spans), in us."""
from harness import layers, spans

spans.install()


def read(ctx):
    seconds = layers.kernel_s(ctx)
    steps = spans.arg_total(ctx, spans.UNPACK, "steps_run")
    if seconds is None or not steps:
        return None
    return seconds / steps * 1e6
