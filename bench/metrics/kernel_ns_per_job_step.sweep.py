"""kernel_ns_per_job_step.sweep: device time of the surrogate executable
over the job-steps the fluid model needs (real jobs x steps to each cell's
last finish), in ns."""
from harness import layers


def read(ctx):
    seconds, steps = layers.kernel_s(ctx), layers.job_steps(ctx)
    if seconds is None or not steps:
        return None
    return seconds / steps * 1e9
