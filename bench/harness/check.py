"""Whether what the timed path answered is correct.

Once the window has closed, a sample of the cells it answered, drawn from
the run's seed, always holding the cell with the longest makespan and, where
the traffic asks, cells from every slice of every request's batch, is
answered again by the cell's plain reference (the one its configuration
names, ``harness.reference`` by default), which regenerates each cell from
its deployment, policy and seed alone.  Each program answer is compared
with the reference's, job by job:

- ``mismatched_cells``: sampled cells whose record is missing or whose
  jobs (ids, count) differ from the reference's job stream;
- ``finish_mismatch_jobs``: jobs finished on one side only;
- ``finish_max_abs_s`` / ``finish_mean_abs_s``: the largest and the mean
  gap in finish time over jobs finished on both sides;
- ``locality_max_abs``: the largest gap in a cell's locality rate.

Each number has the limit the cell's file ``bench/checks/<cell>.json``
gives it; the run is correct when every number is at or under its limit.
"""
from __future__ import annotations

import json
import math
import random
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

import numpy as np

from harness.traffic import derive

NUMBERS = ("mismatched_cells", "finish_mismatch_jobs", "finish_max_abs_s",
           "finish_mean_abs_s", "locality_max_abs")


def limits(root: Path, workload: str) -> Dict[str, float]:
    data = json.loads(
        (Path(root) / "bench" / "checks" / f"{workload}.json").read_text())
    got = data["limits"]
    missing = [k for k in NUMBERS if k not in got]
    if missing:
        raise KeyError(f"checks for {workload} lack limits for {missing}")
    return {k: float(got[k]) for k in NUMBERS}


def record_answer(rec) -> dict:
    """A program ``RunRecord`` in the reference's answer shape."""
    return {"job_ids": [j.job_id for j in rec.jobs],
            "finish": np.array([np.nan if j.finish_time is None
                                else j.finish_time for j in rec.jobs],
                               np.float64),
            "locality_rate": float(rec.locality_rate)}


def sample(groups: Sequence[Sequence[int]], makespans: Sequence[float],
           traffic: dict, workload: str, seed: int) -> List[int]:
    """Indices of the cells to check, drawn from the seed, the longest
    makespan first.  ``groups`` holds each request's cell indices in the
    order the program batched them.  Where the traffic gives
    ``check_per_request``, each request's batch is cut into that many
    equal slices and one cell is drawn from every slice, so every request,
    and both halves of every batch, are checked; otherwise
    ``check_cells`` cells are drawn from the whole window."""
    flat = [i for g in groups for i in g]
    if not flat:
        return []
    longest = max(flat, key=lambda i: makespans[i])
    rng = random.Random(derive(workload, seed, "check"))
    per_request = traffic.get("check_per_request")
    if per_request:
        picked = [longest]
        for g in groups:
            for part in np.array_split(np.asarray(g, int), int(per_request)):
                if part.size:
                    i = int(part[rng.randrange(part.size)])
                    if i not in picked:
                        picked.append(i)
        return picked
    rest = [i for i in flat if i != longest]
    k = int(traffic["check_cells"])
    return [longest] + rng.sample(rest, min(k - 1, len(rest)))


def compare(answers: Sequence[dict], references: Sequence[dict]
            ) -> Dict[str, float]:
    """The numbers compared, over pairs of (answer, reference); an answer
    of ``None`` is a cell the program never answered."""
    out = dict.fromkeys(NUMBERS, 0.0)
    gaps = []
    for got, ref in zip(answers, references):
        if got is None or got["job_ids"] != ref["job_ids"]:
            out["mismatched_cells"] += 1
            continue
        a, b = got["finish"], ref["finish"]
        done_a, done_b = np.isfinite(a), np.isfinite(b)
        out["finish_mismatch_jobs"] += float(np.sum(done_a != done_b))
        both = done_a & done_b
        gaps.append(np.abs(a[both] - b[both]))
        out["locality_max_abs"] = max(
            out["locality_max_abs"],
            abs(got["locality_rate"] - ref["locality_rate"]))
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    if gaps.size:
        out["finish_max_abs_s"] = float(gaps.max())
        out["finish_mean_abs_s"] = float(gaps.mean())
    return out


def verdict(numbers: Dict[str, float], limit: Dict[str, float]) -> bool:
    return all(math.isfinite(numbers[k]) and numbers[k] <= limit[k]
               for k in NUMBERS)


def report(numbers: Dict[str, float], limit: Dict[str, float]) -> dict:
    """The numbers beside their limits, as the result line carries them."""
    return {k: {"value": numbers[k], "limit": limit[k]} for k in NUMBERS}
