"""A deployment's job stream from a published table of job types.

Some deployments are published as a table of job types, each with its
count of jobs and its median bytes (SWIM's k-means clusters of a
cluster's job log), not as a size law.  A configuration whose ``trace``
has ``"kind": "job_types"`` states such a table, and this module turns it
into the rows a user would hand the program (``TraceRef(rows=...)``):
``(workload, input GB, relative deadline, submit time)``.

Every seed gives the same set of jobs and the same set of gaps between
arrivals, in another order:

- each type gets its share of ``num_jobs`` by largest remainder of the
  published counts, and every job of a type takes the type's median
  input;
- the gaps are the ``num_jobs`` mid-quantiles of the exponential law at
  the published mean rate (a Poisson process's gaps, without the draw's
  noise in their sum);
- the seed shuffles the order of the jobs and of the gaps.

A seed's trace is named ``<name>-<seed>`` and carries that seed; the
program's per-cell seed is ``SIM_SEED`` for every such trace.
"""
from __future__ import annotations

import hashlib
import json
import math
import random
from typing import List, Sequence, Tuple

#: the per-cell (simulation) seed of every job-type trace: the trace's own
#: seed already re-rolls placement and jitter
SIM_SEED = 0


def is_job_types(trace: dict) -> bool:
    return trace.get("kind") == "job_types"


def trace_name(trace: dict, seed: int) -> str:
    return f"{trace['name']}-{seed}"


def apportion(weights: Sequence[float], n: int) -> List[int]:
    """``n`` split over ``weights`` by largest remainder (ties to the
    earlier entry)."""
    total = float(sum(weights))
    quotas = [w * n / total for w in weights]
    counts = [int(math.floor(q)) for q in quotas]
    order = sorted(range(len(weights)),
                   key=lambda i: (-(quotas[i] - counts[i]), i))
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return counts


def gaps(rate_per_hour: float, n: int) -> List[float]:
    """The ``n`` mid-quantiles of the exponential law at the rate."""
    lam = rate_per_hour / 3600.0
    return [-math.log(1.0 - (k + 0.5) / n) / lam for k in range(n)]


def _rng(trace: dict, seed: int) -> random.Random:
    blob = json.dumps(["bench-job-types", trace, seed], sort_keys=True,
                      separators=(",", ":"))
    return random.Random(
        int.from_bytes(hashlib.sha256(blob.encode()).digest()[:8], "big"))


def rows(trace: dict, seed: int, deadline) -> List[Tuple[str, float, float,
                                                         float]]:
    """The seed's jobs in arrival order; ``deadline(workload, gb, slack)``
    gives each job's relative deadline."""
    n = int(trace["num_jobs"])
    types = trace["types"]
    counts = apportion([t["jobs"] for t in types], n)
    kinds = [k for k, c in enumerate(counts) for _ in range(c)]
    spacing = gaps(float(trace["rate_per_hour"]), n)
    rng = _rng(trace, seed)
    rng.shuffle(kinds)
    rng.shuffle(spacing)
    out = []
    t = 0.0
    for k, gap in zip(kinds, spacing):
        t += gap
        w, gb = types[k]["workload"], float(types[k]["input_gb"])
        out.append((w, gb, round(deadline(w, gb, trace["deadline_slack"]), 3),
                    round(t, 3)))
    return out
