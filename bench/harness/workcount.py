"""The work the fluid model needs, and the chip's peaks to set it against.

A cell needs ``jobs x steps`` job-steps: its real jobs (padding is the
implementation's choice, not work) times the steps until its last job
finishes (``makespan / DT``; steps past that, and the early exit's chunk
granularity, are the implementation's cost).

The least a job-step must move is the state it reads and updates, counted
once, in float32: the per-job scalars it updates (pending map and reduce
mass, finish time, local and remote launch mass: read and written), the
per-job constants it reads (submit time, absolute deadline, three service
lags, replica share, priority key), and the one column of each of the four
in-flight rings that matures in the step (read and cleared).  Summing whole
rings each step, or keeping running totals, is more than that, so no
implementation can read above 100% of this bound.  The operations are far
below the compute peak, so the bound is bytes at the HBM bandwidth.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Tuple

F32 = 4
#: (updated scalars x read+write) + constants read + maturing ring columns
#: x (read + clear)
BYTES_PER_JOB_STEP = (5 * 2 + 7 + 4 * 2) * F32
#: additions, multiplications and comparisons on those values, per step
OPS_PER_JOB_STEP = 60

DT = 6.0

#: published peaks per chip, keyed by JAX's ``device_kind``
PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "flops_per_s": 197e12,
        "source": "Google Cloud documentation, TPU v5e: 197 TFLOP/s bf16, "
                  "16 GB HBM at 819 GB/s",
    },
}


def peaks(device_kind: str) -> Dict[str, object]:
    """The peaks of ``device_kind``; a kind missing from the table is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {', '.join(sorted(PEAKS))}")
    return PEAKS[device_kind]


def steps_needed(finish_times: Iterable[float]) -> int:
    """Steps until the last finished job's finish time."""
    last = max((t for t in finish_times
                if t is not None and math.isfinite(t) and t < 1e9),
               default=0.0)
    return int(round(last / DT))


def job_steps(jobs: int, finish_times: Iterable[float]) -> int:
    return int(jobs) * steps_needed(finish_times)


def cell_job_steps(pad_mask, finish) -> int:
    """Job-steps of one cell given as kernel rows: ``pad_mask`` marks the
    real jobs, ``finish`` their finish times (padded rows are ignored)."""
    real = [float(t) for m, t in zip(pad_mask, finish) if m > 0.5]
    return job_steps(len(real), real)


def least_seconds(job_steps_total: int, device_kind: str
                  ) -> Tuple[float, str]:
    """The least time for ``job_steps_total`` job-steps on one chip, and
    which bound sets it (``"bytes"`` or ``"flops"``)."""
    peak = peaks(device_kind)
    t_bytes = job_steps_total * BYTES_PER_JOB_STEP / peak["hbm_bytes_per_s"]
    t_ops = job_steps_total * OPS_PER_JOB_STEP / peak["flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "flops")
