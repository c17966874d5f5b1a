"""job_specs_ms_per_cell.sweep: the program's ``repro.surrogate.job_specs``
spans (each trace's job specs, HDFS block placement included) per cell,
in ms."""
from harness import layers, spans

spans.install()


def read(ctx):
    seconds, n = spans.seconds(ctx, spans.JOB_SPECS), layers.per(ctx, "cell")
    if seconds is None or not n:
        return None
    return seconds / n * 1e3
