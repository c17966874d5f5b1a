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
- ``locality_max_abs``: the largest gap in a cell's locality rate;
- ``<name>_max_abs`` for each name in the reference's ``EXTRA_NUMBERS``:
  the largest gap in that number of a cell, which the reference's
  ``answer`` returns and its ``record_numbers(rec)`` reads off the
  program's ``RunRecord``.  A reference without ``EXTRA_NUMBERS`` adds
  none.

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


def extra(reference) -> Tuple[str, ...]:
    """The numbers the reference adds to its answer (``EXTRA_NUMBERS``)."""
    return tuple(getattr(reference, "EXTRA_NUMBERS", ()))


def names(extras: Sequence[str] = ()) -> Tuple[str, ...]:
    """Every number compared where the reference adds ``extras``."""
    return NUMBERS + tuple(f"{n}_max_abs" for n in extras)


def limits(root: Path, workload: str,
           compared: Sequence[str] = NUMBERS) -> Dict[str, float]:
    """The limit of each number in ``compared`` (``names``)."""
    data = json.loads(
        (Path(root) / "bench" / "checks" / f"{workload}.json").read_text())
    got = data["limits"]
    missing = [k for k in compared if k not in got]
    if missing:
        raise KeyError(f"checks for {workload} lack limits for {missing}")
    return {k: float(got[k]) for k in compared}


def record_answer(rec, reference) -> dict:
    """A program ``RunRecord`` in the reference's answer shape, with the
    numbers the reference reads off it (``record_numbers``)."""
    out = {"job_ids": [j.job_id for j in rec.jobs],
           "finish": np.array([np.nan if j.finish_time is None
                               else j.finish_time for j in rec.jobs],
                              np.float64),
           "locality_rate": float(rec.locality_rate)}
    if extra(reference):
        out.update(reference.record_numbers(rec))
    return out


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


def compare(answers: Sequence[dict], references: Sequence[dict],
            extras: Sequence[str] = ()) -> Dict[str, float]:
    """The numbers compared, over pairs of (answer, reference); an answer
    of ``None`` is a cell the program never answered.  ``extras`` are the
    reference's ``EXTRA_NUMBERS``, each read like the locality rate."""
    out = dict.fromkeys(names(extras), 0.0)
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
        for n in extras:
            # np.maximum keeps a NaN gap, which the verdict then fails
            out[f"{n}_max_abs"] = float(np.maximum(
                out[f"{n}_max_abs"], abs(got[n] - ref[n])))
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    if gaps.size:
        out["finish_max_abs_s"] = float(gaps.max())
        out["finish_mean_abs_s"] = float(gaps.mean())
    return out


def verdict(numbers: Dict[str, float], limit: Dict[str, float]) -> bool:
    """Every number that has a limit is at or under it."""
    return all(math.isfinite(numbers[k]) and numbers[k] <= limit[k]
               for k in limit)


def report(numbers: Dict[str, float], limit: Dict[str, float]) -> dict:
    """The numbers beside their limits, as the result line carries them."""
    return {k: {"value": numbers[k], "limit": limit[k]} for k in limit}
