"""The control: the plain reference computed in bfloat16, put in the
program's place, must come out not correct against the float32 reference
under each cell's own limits."""
import json

import ml_dtypes
import numpy as np
import pytest

from conftest import BENCH, ROOT
from harness import check, program
from harness import reference as R
from harness.traffic import Stream


def _config(name, num_jobs=None):
    config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    if num_jobs is not None:
        config["num_jobs"] = num_jobs
    config["trace"] = program.trace_recipe(config)
    return config


def _control_numbers(config, cells):
    answers, refs = [], []
    for policy, seed in cells:
        cell = R.build(config, policy, seed)
        refs.append(R.answer(config, policy, seed, cell=cell))
        answers.append(R.answer(config, policy, seed,
                                dtype=ml_dtypes.bfloat16, cell=cell))
    return check.compare(answers, refs)


@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2, 2**31 + 3])
def test_control_fails_query_cell(seed):
    """At the query cell's own size: 80 jobs at 20x2, two queries of the
    run's stream over the four paired seeds."""
    traffic = json.loads((BENCH / "traffic" / "query.json").read_text())
    stream = Stream(traffic, "query.paper_20x2", seed)
    cells = [(p, s) for k in range(2) for req in [stream.request(k)]
             for p in req.policies for s in req.seeds]
    numbers = _control_numbers(_config("paper_20x2"), cells)
    limits = check.limits(ROOT, "query.paper_20x2")
    assert not check.verdict(numbers, limits), numbers


@pytest.mark.parametrize("seed", [2**31 + 1, 2**31 + 2, 2**31 + 3])
def test_control_fails_grid_cell(seed):
    """The grid cell's deployment at a test's size (300 of its 1,000 jobs)
    and one cell of its first request."""
    traffic = json.loads((BENCH / "traffic" / "grid.json").read_text())
    req = Stream(traffic, "grid.fb2009_600x2", seed).request(0)
    cells = [(req.policies[seed % len(req.policies)], req.seeds[0])]
    numbers = _control_numbers(_config("fb2009_600x2", 300), cells)
    limits = check.limits(ROOT, "grid.fb2009_600x2")
    assert not check.verdict(numbers, limits), numbers


def test_float32_reference_passes_against_itself():
    config = _config("paper_20x2", 20)
    policy = {"name": "proposed", "params": {"max_wait": 20.0}}
    a = R.answer(config, policy, 5)
    numbers = check.compare([a], [a])
    assert check.verdict(numbers, check.limits(ROOT, "query.paper_20x2"))
    assert np.all(np.isfinite(a["finish"]))
