"""The work count and the peaks table."""
import numpy as np
import pytest

from harness import workcount


def test_count_ignores_padding():
    """A cell's job-steps are the same in its unpadded and padded (kernel
    row) forms: padded rows are not work."""
    finish = np.array([60.0, 1230.0, 600.0], np.float32)
    unpadded = workcount.job_steps(3, finish.tolist())
    pad_mask = np.zeros(8, np.float32)
    pad_mask[:3] = 1.0
    padded_finish = np.full(8, 3.0e9, np.float32)
    padded_finish[:3] = finish
    assert workcount.cell_job_steps(pad_mask, padded_finish) == unpadded
    assert unpadded == 3 * 205


def test_unfinished_jobs_add_no_steps():
    assert workcount.steps_needed([None, 120.0, float("inf"), 3.0e9]) == 20


def test_least_time_is_bytes_bound_on_v5e():
    seconds, bound = workcount.least_seconds(10**9, "TPU v5 lite")
    assert bound == "bytes"
    assert seconds == pytest.approx(
        10**9 * workcount.BYTES_PER_JOB_STEP / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        workcount.peaks("TPU v99")
    with pytest.raises(KeyError):
        workcount.least_seconds(1, "cpu")
