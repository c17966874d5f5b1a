"""kernel_ring_ns_per_job_step.sweep: exclusive device time of the kernel's
operations in its ring stages (``ring_drain``, ``ring_scatter``: the
in-flight rings' clears, sums and scatters, by the program's
``kernel_stages``) over the job-steps the fluid model needs, in ns."""
from harness import layers, spans

spans.install()


def read(ctx):
    stages, steps = spans.stage_seconds(ctx), layers.job_steps(ctx)
    if not stages or not steps:
        return None
    return sum(stages.get(s, 0.0) for s in spans.RING_STAGES) / steps * 1e9
