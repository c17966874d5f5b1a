"""Surrogate-engine benchmark: batched fluid sweeps vs the event oracle.

Runs a fleet-scale sweep grid — the heavy_tail atlas trace over a
200-machine fleet, every surrogate-lowerable policy, many paired seeds —
through the batched fluid engine in ONE ``run_batch`` call, times the
event engine on a sample of the same cells, and records surrogate
cells/sec, event-engine cells/sec and their ratio into the ``surrogate``
section of ``BENCH_sim.json`` (git-commit and engine-id stamped, same
regression-tracking contract as the ``scenarios`` section).

The grid is where the batch engine is structurally strong: the event
engine's cost grows with fleet size (every VM heartbeats through the
whole makespan) while the fluid kernel folds machine capacity into two
scalars, so a fleet-scale what-if sweep is exactly the workload the
surrogate exists for.  The surrogate-side timing is end-to-end — trace
resolution, cell compilation (shared across the grid's policy columns,
as ``run_surrogate`` shares it) and the batched integration — but
excludes one-time XLA compilation, which is reported separately.

Modes:

* default — 1000 cells (5 policies x 200 seeds) in one batched run;
* ``--quick`` — 100 cells (5 policies x 20 seeds) for per-PR regression
  tracking in CI.

Usage::

    PYTHONPATH=src python benchmarks/bench_surrogate.py [--quick] [--out PATH]
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO_ROOT / "src"))

from repro.core.types import ClusterSpec                         # noqa: E402
from repro.experiments.runner import (ExperimentSpec, TraceRef,  # noqa: E402
                                      simulate_cell)
from repro.experiments.surrogate import build_inputs             # noqa: E402
from repro.simcluster.surrogate import (SURROGATE_ENGINE_ID,     # noqa: E402
                                        run_batch, use_compile_cache)

EVENT_ENGINE_ID = "simcluster.sim/incremental-index"
POLICIES = ("proposed", "fair", "fifo", "delay", "edf_nopark")
#: cells/sec advantage the batched engine must sustain on this grid
TARGET_RATIO = 50.0


def git_commit() -> str:
    """Short HEAD hash, with ``-dirty`` when the tree has uncommitted
    changes — numbers from uncommitted code must not impersonate a commit."""
    try:
        commit = subprocess.run(
            ["git", "-C", str(REPO_ROOT), "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, check=True,
            timeout=10).stdout.strip()
        status = subprocess.run(
            ["git", "-C", str(REPO_ROOT), "status", "--porcelain"],
            capture_output=True, text=True, check=True, timeout=10).stdout
        return commit + ("-dirty" if status.strip() else "")
    except Exception:
        return "unknown"


def sweep_spec(n_seeds: int) -> ExperimentSpec:
    return ExperimentSpec(
        name="bench-surrogate-fleet",
        traces=(TraceRef(preset="heavy_tail"),),
        clusters=(ClusterSpec(num_machines=200, vms_per_machine=2,
                              replication=2),),
        schedulers=POLICIES,
        seeds=tuple(range(n_seeds)))


def bench(n_seeds: int, event_sample: int, commit: str) -> dict:
    spec = sweep_spec(n_seeds)
    cells = list(spec.cells())
    print(f"[bench] building {len(cells)} surrogate cells "
          f"({len(POLICIES)} policies x {n_seeds} seeds) ...", flush=True)
    t0 = time.perf_counter()
    _, inputs = build_inputs(cells)
    t_build = time.perf_counter() - t0
    # one warmup batch triggers XLA compilation for the bucket; the timed
    # run below then measures steady-state sweep throughput (a repeat
    # sweep of a new grid, the common case for atlas exploration)
    print("[bench] compiling kernel (warmup batch) ...", flush=True)
    t0 = time.perf_counter()
    run_batch(inputs[:1])
    t_compile = time.perf_counter() - t0
    print(f"[bench] integrating {len(inputs)} cells in one batched run ...",
          flush=True)
    t0 = time.perf_counter()
    results = run_batch(inputs)
    t_integrate = time.perf_counter() - t0
    finished = sum(r.jobs_finished for r in results)
    t_cell = (t_build + t_integrate) / len(cells)

    print(f"[bench] event engine on {event_sample} sample cells ...",
          flush=True)
    t0 = time.perf_counter()
    for cell in cells[:event_sample]:
        simulate_cell(cell)
    t_event = (time.perf_counter() - t0) / event_sample

    ratio = t_event / t_cell
    return {
        "description": ("heavy_tail trace x 200x2 fleet x "
                        f"{len(POLICIES)} policies x {n_seeds} seeds, "
                        "all cells in one batched run"),
        "surrogate": {
            "engine_id": SURROGATE_ENGINE_ID,
            "git_commit": commit,
            "cells": len(cells),
            "build_time_s": round(t_build, 3),
            "compile_time_s": round(t_compile, 3),
            "integrate_time_s": round(t_integrate, 3),
            "cells_per_sec": round(1.0 / t_cell, 1),
            "jobs_finished": finished,
        },
        "event": {
            "engine_id": EVENT_ENGINE_ID,
            "git_commit": commit,
            "sample_cells": event_sample,
            "wall_time_s_per_cell": round(t_event, 3),
            "cells_per_sec": round(1.0 / t_event, 3),
        },
        "speedup": round(ratio, 1),
        "target_speedup": TARGET_RATIO,
        "meets_target": ratio >= TARGET_RATIO,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="100-cell subset for per-PR regression tracking")
    ap.add_argument("--out", type=Path, default=REPO_ROOT / "BENCH_sim.json")
    args = ap.parse_args(argv)

    commit = git_commit()
    use_compile_cache()
    entry = bench(n_seeds=20 if args.quick else 200,
                  event_sample=2 if args.quick else 4, commit=commit)
    entry["mode"] = "quick" if args.quick else "full"

    # merge into BENCH_sim.json without disturbing the event-engine
    # scenario benchmarks that live alongside
    doc_text = args.out.read_text() if args.out.exists() else ""
    doc = json.loads(doc_text) if doc_text.strip() else {}
    doc["surrogate"] = entry
    args.out.write_text(json.dumps(doc, indent=2) + "\n")
    print(f"[bench] wrote {args.out}")
    s, e = entry["surrogate"], entry["event"]
    print(f"  surrogate: {s['cells']} cells, {s['cells_per_sec']} cells/s "
          f"(build {s['build_time_s']}s + integrate {s['integrate_time_s']}s"
          f", compile {s['compile_time_s']}s excluded)")
    print(f"  event:     {e['cells_per_sec']} cells/s "
          f"({e['wall_time_s_per_cell']}s/cell over {e['sample_cells']} cells)")
    print(f"  speedup:   {entry['speedup']}x (target {TARGET_RATIO:.0f}x, "
          f"{'MET' if entry['meets_target'] else 'MISSED'})")
    # the target is enforced on the full grid; the quick subset amortizes
    # build cost over 10x fewer cells and is tracked by scripts/check.sh
    # against the committed number instead
    return 0 if (entry["meets_target"] or args.quick) else 1


if __name__ == "__main__":
    raise SystemExit(main())
