#!/usr/bin/env bash
# CI gate on the host CPU: tier-1 tests, then the smoke checks of each
# subsystem and a paired bound on the decision-trace bus's overhead.  It
# reads no committed baseline: speed on the chip is what bench/run.py
# measures.
#
#   scripts/check.sh                # full gate
#   SKIP_TESTS=1 scripts/check.sh   # smoke checks only
#   TRACE_TOL=0.1 scripts/check.sh  # allowed enabled-tracing overhead
set -euo pipefail

REPO_ROOT="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
cd "$REPO_ROOT"
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

TRACE_TOL="${TRACE_TOL:-0.10}"

if [[ "${SKIP_TESTS:-0}" != "1" ]]; then
    # The differential fuzz / invariant suites are part of tier-1 with a
    # deterministic bounded budget: a fixed scenario-seed base and example
    # caps (and, when the optional hypothesis extra is installed, the
    # derandomized `tier1` profile registered in tests/test_parity_fuzz.py).
    # Raise REPRO_FUZZ_SCENARIOS / REPRO_ADAPTIVE_FUZZ_SCENARIOS or switch
    # HYPOTHESIS_PROFILE=dev for deeper local exploration.
    export REPRO_FUZZ_SCENARIOS="${REPRO_FUZZ_SCENARIOS:-200}"
    export REPRO_ADAPTIVE_FUZZ_SCENARIOS="${REPRO_ADAPTIVE_FUZZ_SCENARIOS:-60}"
    export REPRO_FAULT_FUZZ_SCENARIOS="${REPRO_FAULT_FUZZ_SCENARIOS:-60}"
    export REPRO_FUZZ_SEED="${REPRO_FUZZ_SEED:-0}"
    export HYPOTHESIS_PROFILE="${HYPOTHESIS_PROFILE:-tier1}"
    echo "== tier-1 tests (fast suite, -m 'not fuzz') =="
    python -m pytest -x -q -m "not fuzz"
    echo "== fuzz profile (legacy parity x ${REPRO_FUZZ_SCENARIOS} + adaptive liveness x ${REPRO_ADAPTIVE_FUZZ_SCENARIOS} + chaos liveness x ${REPRO_FAULT_FUZZ_SCENARIOS}) =="
    python -m pytest -x -q -m fuzz
fi

echo "== policy smoke (every registered policy on a tiny cluster) =="
python -m repro.experiments policies --smoke

echo "== adaptive smoke (win recovery on saturated; no CI-clear churn loss) =="
# One saturated and one churn_hi quick cell through the cached experiment
# runner (first run simulates ~2x6x2 paired seeds, later runs hit the
# cache).  Guards the two failure modes PR 8 fixed: the overload latch
# surrendering the closed-mix parking win back to exact-Fair (+0.0), and
# the adaptive gates losing to the fixed proposed policy under churn with
# a CI excluding zero.
#
# If this gate fails with adaptive-vs-fair exactly +0.0 on a machine that
# last ran sweeps before PR 8: the bugfix deliberately kept the adaptive
# cells' cache keys (see ClusterSpec.to_dict — default-valued knobs are
# omitted so the pinned cell hashes stay), so a pre-PR-8 cache serves
# stale pre-fix results.  Delete the cache dir once and re-run.
ADAPTIVE_SMOKE_CACHE="${ADAPTIVE_SMOKE_CACHE:-.exp-cache}"
python - "$ADAPTIVE_SMOKE_CACHE" <<'PY'
import sys

from repro.experiments.regimes import QUICK_SEEDS, regime_spec
from repro.experiments.runner import run_experiment
from repro.experiments.stats import compare_throughput

cache = sys.argv[1]
failures = []

# saturated/50x2: the closed-mix cell where the latch used to stand the
# adaptive columns down to exact Fair.  Require a real recovered win:
# CI clear of zero vs Fair and at least half the fixed policy's gain.
by = run_experiment(regime_spec("saturated", "50x2", seeds=QUICK_SEEDS),
                    cache).by_scheduler()
ad = compare_throughput(by["fair"], by["adaptive"])
px = compare_throughput(by["fair"], by["proposed"])
print(f"  saturated/50x2: adaptive vs fair {ad.mean_gain_pct:+.1f}% "
      f"[{ad.ci_lo_pct:+.1f}%, {ad.ci_hi_pct:+.1f}%] "
      f"(proposed {px.mean_gain_pct:+.1f}%)")
if ad.mean_gain_pct == 0.0:
    failures.append("saturated/50x2: adaptive surrendered to exact Fair (+0.0)")
elif not (ad.ci_lo_pct > 0.0 and ad.mean_gain_pct >= 0.5 * px.mean_gain_pct):
    failures.append(
        f"saturated/50x2: adaptive win {ad.mean_gain_pct:+.1f}% "
        f"[{ad.ci_lo_pct:+.1f}%, ...] does not recover half of the fixed "
        f"policy's {px.mean_gain_pct:+.1f}%")

# churn_hi/20x2: under crash churn the relief gates must never make the
# adaptive column lose to the fixed policy with a CI excluding zero.
by = run_experiment(regime_spec("saturated", "20x2", seeds=QUICK_SEEDS,
                                faults="churn_hi"),
                    cache).by_scheduler()
vp = compare_throughput(by["proposed"], by["adaptive"])
print(f"  saturated/20x2/churn_hi: adaptive vs proposed "
      f"{vp.mean_gain_pct:+.1f}% [{vp.ci_lo_pct:+.1f}%, {vp.ci_hi_pct:+.1f}%]")
if vp.ci_hi_pct < 0.0:
    failures.append(
        f"saturated/20x2/churn_hi: adaptive loses to fixed with CI "
        f"excluding zero [{vp.ci_lo_pct:+.1f}%, {vp.ci_hi_pct:+.1f}%]")

if failures:
    print("\nFAIL:")
    for f in failures:
        print(f"  - {f}")
    sys.exit(1)
print("  adaptive smoke passed")
PY

echo "== fault-injection smoke (churn fleet drains; schedule reproducible) =="
python - <<'PY'
from repro.simcluster.largescale import run_scenario

res = run_scenario("fleet_100x2_churn", scheduler="proposed", seed=0)
assert res.fault_stats["crashes"] > 0, res.fault_stats
unfinished = [j for j, r in res.jobs.items() if r.finish_time is None]
assert not unfinished, f"jobs never finished under churn: {unfinished[:5]}"
again = run_scenario("fleet_100x2_churn", scheduler="proposed", seed=0)
assert again.fault_log == res.fault_log, "fault schedule not reproducible"
print(f"  crashes={res.fault_stats['crashes']} "
      f"lost={res.fault_stats['tasks_lost']} "
      f"reexecuted={res.fault_stats['tasks_reexecuted']} "
      f"bursts={res.fault_stats['bursts']} — all "
      f"{len(res.jobs)} jobs finished; log byte-reproducible")
PY

echo "== serving smoke (SLO fleet drains; request log reproducible; harvest reconciles) =="
python - <<'PY'
import json

from repro.simcluster.largescale import SCENARIOS, run_scenario

res = run_scenario("fleet_100x2_serving", scheduler="harvest", seed=0,
                   tracing=True)
unfinished = [j for j, r in res.jobs.items() if r.finish_time is None]
assert not unfinished, f"batch jobs never finished: {unfinished[:5]}"
st = res.serve_stats
assert st["requests"] > 0, "service fleet received no requests"
bound = SCENARIOS["fleet_100x2_serving"].serve.slo_violation_bound
assert st["violation_rate"] <= bound, (
    f"SLO violation rate {st['violation_rate']:.4f} > bound {bound}")
# harvest events on the trace bus reconcile with the reconfigurator
# counters and the serving layer's own ledger
assert res.trace.count("harvest_borrow") == st["harvest_borrows"] \
    == res.reconfig_stats["harvest_borrows"], "borrow ledgers disagree"
assert res.trace.count("harvest_return") == st["harvest_returns"] \
    == res.reconfig_stats["harvest_returns"], "return ledgers disagree"
assert st["harvest_borrows"] - st["harvest_returns"] \
    == st["outstanding_borrows"], "harvest ledger leak"
# request log byte-reproducible across two identical runs
again = run_scenario("fleet_100x2_serving", scheduler="harvest", seed=0,
                     tracing=True)
assert json.dumps(again.serve_log) == json.dumps(res.serve_log), \
    "serve request log not byte-reproducible"
assert again.serve_stats == st, "serving stats not reproducible"
print(f"  requests={st['requests']} shed={st['shed']} "
      f"p99={st['p99_ms']:.0f}ms viol_rate={st['violation_rate']:.4f} "
      f"(bound {bound}); harvest {st['harvest_borrows']} borrows / "
      f"{st['harvest_returns']} returns — ledgers reconcile, "
      f"log byte-reproducible")
PY

echo "== trace smoke (traced churn run byte-reproducible; explain exits 0) =="
python - <<'PY'
from repro.simcluster.largescale import run_scenario

plain = run_scenario("fleet_100x2_churn", scheduler="proposed", seed=0)
traced = run_scenario("fleet_100x2_churn", scheduler="proposed", seed=0,
                      tracing=True)
assert traced.makespan == plain.makespan, \
    "tracing changed the schedule under churn"
assert traced.fault_log == plain.fault_log, \
    "tracing changed the fault schedule"
again = run_scenario("fleet_100x2_churn", scheduler="proposed", seed=0,
                     tracing=True)
assert again.trace.to_jsonl() == traced.trace.to_jsonl(), \
    "trace not byte-reproducible across identical runs"
print(f"  {traced.trace.total} events, JSONL byte-identical across runs, "
      f"makespan/fault_log unchanged vs untraced")
PY
EXPLAIN_CACHE="$(mktemp -d /tmp/explain_cache.XXXXXX)"
python -m repro.experiments explain saturated 20x2 \
    --cache "$EXPLAIN_CACHE" --no-store > /dev/null
rm -rf "$EXPLAIN_CACHE"
echo "  explain verb exited 0"

echo "== surrogate smoke (calibrated sweep + differential gate on heavy_tail) =="
# The surrogate verb sweeps the allowlisted 20x2 heavy_tail grid through
# the batched fluid engine, then re-runs the differential calibration
# against the event oracle on the pinned seeds and exits 1 on drift.
# Shares the persistent cache with the adaptive smoke — surrogate cells
# hash into a disjoint engine namespace, so the two engines coexist.
python -m repro.experiments surrogate heavy_tail --shape 20x2 \
    --seeds 0:4 --cache "$ADAPTIVE_SMOKE_CACHE"
echo "  surrogate smoke passed"

echo "== enabled-tracing overhead bound (tol ${TRACE_TOL}) =="
python - "$TRACE_TOL" <<'PY'
import sys, time
from repro.simcluster.largescale import run_scenario

tol = float(sys.argv[1])

# Paired CPU-time reps: each pair runs untraced then traced back-to-back
# and records the traced/untraced ratio.  Single measurements on shared
# CI machines swing far more (±15-25%) than the ~10% overhead being
# bounded, so the gate passes if the *cleanest* of five pairs is within
# tolerance — noise is symmetric, so a genuine regression (an allocation
# or stringification landing back on the launch hot path) pushes every
# pair over the bar, while honest ~10% overhead always yields at least
# one clean pair.
def timed(**kw):
    c0 = time.process_time()
    r = run_scenario("fleet_100x2_sustained", seed=0, **kw)
    return time.process_time() - c0, r

overheads = []
for _ in range(5):
    cpu_u, plain = timed()
    cpu_t, traced = timed(tracing=True)
    assert traced.makespan == plain.makespan, "tracing changed the schedule"
    overheads.append(cpu_t / cpu_u - 1.0)
    print(f"  untraced {cpu_u:.3f} cpu-s, traced {cpu_t:.3f} cpu-s "
          f"({traced.trace.total} trace events): overhead "
          f"{overheads[-1]:+.1%}")
best = min(overheads)
print(f"  best of {len(overheads)} pairs: {best:+.1%} (bound {tol:.0%})")
if best > tol:
    print(f"FAIL: enabled-tracing overhead {best:.1%} > {tol:.0%} "
          f"in every pair")
    sys.exit(1)
print("  enabled-tracing overhead bound passed")
PY

echo "== all checks passed =="
