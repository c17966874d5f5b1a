"""kernel_roofline.sweep: the least time of the job-steps the fluid model
needs (bytes at the chip's HBM bandwidth, harness.workcount) over the
surrogate executable's device time, in %."""
from harness import layers, workcount


def read(ctx):
    seconds, steps = layers.kernel_s(ctx), layers.job_steps(ctx)
    if seconds is None or not steps:
        return None
    least, _ = workcount.least_seconds(steps, ctx.device_kind)
    return least / seconds * 100.0
