"""kernel_lane_step_use.sweep: the job-steps the fluid model needs (real
jobs x steps to each cell's last finish) over the job-steps the kernel ran:
lanes x padded jobs x steps run, summed over sub-batches from the
program's ``unpack`` spans.  Padding, the chunks' overrun and lanes that
wait on the slowest lane of their sub-batch make up the rest."""
from harness import layers, spans

spans.install()


def read(ctx):
    ran = spans.arg_total(ctx, spans.UNPACK, "lanes", "jobs", "steps_run")
    needed = layers.job_steps(ctx)
    if not ran or not needed:
        return None
    return needed / ran
