"""Simulation-engine benchmark: indexed engine vs the frozen seed engine.

Runs large-fleet scenarios from ``repro.simcluster.largescale`` on the
optimized (incremental-index) engine and, where the seed engine can run them
at all, on the frozen legacy engine, and reports wall time, events/sec and
the speedup ratio per scenario as JSON: on stdout, or in ``--out PATH``.
These are host-CPU numbers; no baseline of them is kept in the repo.

Modes:

* default — the full regression benchmark: paper cluster (both engines) +
  the sustained 100-machine / 120-job scenario (both engines, ≥10× target)
  + the larger indexed-only fleets + the fault-injection churn fleet;
* ``--quick`` — < 60 s subset for per-PR regression tracking: paper cluster
  (both engines) + the smoke fleet (both engines) + the sustained and
  churn 100-machine fleets on the indexed engine only.

Usage::

    PYTHONPATH=src python benchmarks/bench_sim.py [--quick] [--out PATH]
    PYTHONPATH=src python benchmarks/bench_sim.py --scenarios fleet_200x4
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

# engine identifiers stamped into every per-engine entry so the perf
# trajectory across PRs stays attributable to a specific implementation
ENGINE_IDS = {
    "indexed": "simcluster.sim/incremental-index",
    "legacy": "simcluster._legacy/seed-frozen",
}


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

from repro.core.reconfigurator import Reconfigurator            # noqa: E402
from repro.core.scheduler import CompletionTimeScheduler        # noqa: E402
from repro.simcluster._legacy import (LegacyClusterSim,         # noqa: E402
                                      LegacyCompletionTimeScheduler,
                                      LegacyReconfigurator)
from repro.simcluster.largescale import SCENARIOS, run_scenario  # noqa: E402
from repro.simcluster.sim import ClusterSim                      # noqa: E402
from repro.simcluster.workloads import (paper_cluster,           # noqa: E402
                                        paper_table2_jobs)


def _summarize(result, wall: float, engine: str, commit: str) -> dict:
    done = sum(1 for j in result.jobs.values() if j.finish_time is not None)
    return {
        "engine_id": ENGINE_IDS[engine],
        "git_commit": commit,
        "wall_time_s": round(wall, 4),
        "events": result.events_processed,
        "events_per_sec": round(result.events_processed / wall, 1) if wall else None,
        "sim_makespan_s": round(result.makespan, 2),
        "jobs_finished": done,
        "jobs_total": len(result.jobs),
        "deadlines_met": result.deadlines_met(),
        "locality_rate": round(result.locality_rate(), 4),
        "speculative_launches": result.speculative_launches,
    }


def log(*parts, **kw) -> None:
    """Progress and the summary go to stderr: stdout carries only the
    JSON."""
    print(*parts, file=sys.stderr, **kw)


def bench_paper_cluster(seed: int = 3, commit: str = "unknown") -> dict:
    """Paper-sized cluster on both engines (also a live parity check)."""
    out = {}
    spec = paper_cluster()
    for engine in ("indexed", "legacy"):
        if engine == "indexed":
            sched = CompletionTimeScheduler(spec, Reconfigurator(spec, max_wait=30.0))
            sim = ClusterSim(spec, sched, seed=seed)
        else:
            sched = LegacyCompletionTimeScheduler(
                spec, LegacyReconfigurator(spec, max_wait=30.0))
            sim = LegacyClusterSim(spec, sched, seed=seed)
        t0 = time.perf_counter()
        res = sim.run(paper_table2_jobs(spec, seed=seed))
        out[engine] = _summarize(res, time.perf_counter() - t0, engine, commit)
    out["speedup"] = round(out["legacy"]["wall_time_s"]
                           / out["indexed"]["wall_time_s"], 2)
    out["parity"] = (out["indexed"]["sim_makespan_s"]
                     == out["legacy"]["sim_makespan_s"])
    return out


def bench_scenario(name: str, *, seed: int = 0, engines=("indexed",),
                   commit: str = "unknown", traced: bool = False) -> dict:
    out: dict = {"description": SCENARIOS[name].description}
    for engine in engines:
        t0 = time.perf_counter()
        res = run_scenario(name, engine=engine, seed=seed)
        out[engine] = _summarize(res, time.perf_counter() - t0, engine, commit)
    if traced:
        # same scenario with the decision-trace bus enabled: measures the
        # observer overhead and live-checks the bit-exactness contract.
        # Best-of-3 because single-shot wall clocks on shared machines
        # swing far more than the overhead being measured.
        best_wall, res = None, None
        for _ in range(3):
            t0 = time.perf_counter()
            r = run_scenario(name, engine="indexed", seed=seed, tracing=True)
            wall = time.perf_counter() - t0
            if best_wall is None or wall < best_wall:
                best_wall, res = wall, r
        entry = _summarize(res, best_wall, "indexed", commit)
        entry["engine_id"] += "+trace-bus"
        entry["trace_events"] = res.trace.total
        out["indexed_traced"] = entry
        out["traced_parity"] = (entry["sim_makespan_s"]
                                == out["indexed"]["sim_makespan_s"])
        out["trace_overhead_pct"] = round(
            100.0 * (1.0 - entry["events_per_sec"]
                     / out["indexed"]["events_per_sec"]), 1)
    if "legacy" in out and "indexed" in out:
        out["speedup"] = round(out["legacy"]["wall_time_s"]
                               / out["indexed"]["wall_time_s"], 2)
        out["parity"] = (out["indexed"]["sim_makespan_s"]
                         == out["legacy"]["sim_makespan_s"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="<60s subset for per-PR regression tracking")
    ap.add_argument("--scenarios", nargs="+", default=None,
                    help="explicit scenario names (indexed engine only)")
    ap.add_argument("--traced", action="store_true",
                    help="also run each scenario with the decision-trace "
                         "bus enabled: records indexed_traced events/sec, "
                         "the overhead %% and a traced-parity check")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=Path, default=None,
                    help="write the JSON here (default: print it on stdout)")
    args = ap.parse_args(argv)

    commit = git_commit()
    results: dict = {"mode": "quick" if args.quick else "full",
                     "seed": args.seed, "git_commit": commit,
                     "scenarios": {}}
    t_start = time.perf_counter()

    if args.scenarios:
        unknown = [n for n in args.scenarios if n not in SCENARIOS]
        if unknown:
            ap.error(f"unknown scenario(s) {unknown}; "
                     f"available: {', '.join(sorted(SCENARIOS))}")
        for name in args.scenarios:
            log(f"[bench] {name} (indexed"
                + (" + traced" if args.traced else "") + ") ...",
                flush=True)
            results["scenarios"][name] = bench_scenario(
                name, seed=args.seed, commit=commit, traced=args.traced)
    else:
        log("[bench] paper cluster (indexed + legacy) ...", flush=True)
        results["scenarios"]["paper_20x2"] = bench_paper_cluster(commit=commit)
        log("[bench] smoke_40x2 (indexed) ...", flush=True)
        results["scenarios"]["smoke_40x2"] = bench_scenario(
            "smoke_40x2", seed=args.seed, commit=commit)
        if args.quick:
            log("[bench] fleet_100x2_sustained (indexed) ...", flush=True)
            results["scenarios"]["fleet_100x2_sustained"] = bench_scenario(
                "fleet_100x2_sustained", seed=args.seed, commit=commit,
                traced=args.traced)
            log("[bench] fleet_100x2_churn (indexed) ...", flush=True)
            results["scenarios"]["fleet_100x2_churn"] = bench_scenario(
                "fleet_100x2_churn", seed=args.seed, commit=commit,
                traced=args.traced)
            log("[bench] fleet_100x2_serving (indexed) ...", flush=True)
            results["scenarios"]["fleet_100x2_serving"] = bench_scenario(
                "fleet_100x2_serving", seed=args.seed, commit=commit,
                traced=args.traced)
        else:
            # the headline comparison: >=100 machines, >=100 jobs, both
            # engines.  The arrival trace is gap-free so the seed engine's
            # heartbeat deadlock does not bias the measurement.
            log("[bench] fleet_100x2_sustained (indexed + legacy, "
                "the legacy run takes minutes) ...", flush=True)
            results["scenarios"]["fleet_100x2_sustained"] = bench_scenario(
                "fleet_100x2_sustained", seed=args.seed,
                engines=("indexed", "legacy"), commit=commit,
                traced=args.traced)
            for name in ("fleet_100x2", "fleet_200x2", "fleet_200x4",
                         "fleet_400x2", "burst_idle_gap"):
                log(f"[bench] {name} (indexed; impossible on the seed "
                    "engine: idle-gap deadlock / intractable scan cost) ...",
                    flush=True)
                results["scenarios"][name] = bench_scenario(
                    name, seed=args.seed, commit=commit)
            log("[bench] fleet_100x2_churn (indexed; fault injection "
                "does not exist on the seed engine) ...", flush=True)
            results["scenarios"]["fleet_100x2_churn"] = bench_scenario(
                "fleet_100x2_churn", seed=args.seed, commit=commit)
            log("[bench] fleet_100x2_serving (indexed; the serving layer "
                "does not exist on the seed engine) ...", flush=True)
            results["scenarios"]["fleet_100x2_serving"] = bench_scenario(
                "fleet_100x2_serving", seed=args.seed, commit=commit)

    results["total_wall_time_s"] = round(time.perf_counter() - t_start, 2)
    text = json.dumps(results, indent=2) + "\n"
    if args.out is None:
        sys.stdout.write(text)
    else:
        args.out.write_text(text)
        log(f"[bench] wrote {args.out}")
    for name, r in results["scenarios"].items():
        line = f"  {name}: "
        if "indexed" in r:
            line += (f"{r['indexed']['wall_time_s']}s, "
                     f"{r['indexed']['events_per_sec']} ev/s")
        if "speedup" in r:
            line += f", speedup {r['speedup']}x, parity={r['parity']}"
        if "indexed_traced" in r:
            line += (f", traced {r['indexed_traced']['events_per_sec']} ev/s "
                     f"({r['trace_overhead_pct']:+.1f}% overhead, "
                     f"traced_parity={r['traced_parity']})")
        log(line)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
