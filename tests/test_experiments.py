"""Experiment harness: sweep caching, paired statistics, and the statistical
reproduction of the paper's §5 claims through the trace-driven path."""
import json
import math

import pytest

from repro.core.types import ClusterSpec
from repro.experiments.metrics import RunRecord
from repro.experiments.paperfig import FULL_SEEDS, QUICK_SEEDS, run_paper
from repro.experiments.runner import (ExperimentSpec, TraceRef,
                                      run_experiment, simulate_cell)
from repro.experiments.stats import (bootstrap_mean_ci,
                                     compare_completion_by_workload,
                                     compare_throughput, paired_bootstrap)
from repro.simcluster.traces import PRESETS, TraceConfig, generate_trace


def _small_spec(seeds=(0, 1), schedulers=("proposed", "fair"), trace_seed=0):
    return ExperimentSpec(
        name="t",
        traces=(TraceRef(preset="mix_small", seed=trace_seed),),
        clusters=(ClusterSpec(num_machines=6, vms_per_machine=2,
                              replication=1),),
        schedulers=schedulers,
        seeds=seeds,
    )


# -- cache behaviour --------------------------------------------------------

def test_rerun_hits_cache_zero_new_sims(tmp_path):
    spec = _small_spec()
    first = run_experiment(spec, tmp_path)
    assert first.simulated == 4 and first.cached == 0
    again = run_experiment(spec, tmp_path)
    assert again.simulated == 0 and again.cached == 4
    assert [r.to_dict() for r in again.records] \
        == [r.to_dict() for r in first.records]


def test_partial_grid_runs_only_missing_cells(tmp_path):
    run_experiment(_small_spec(seeds=(0, 1)), tmp_path)
    grown = run_experiment(_small_spec(seeds=(0, 1, 2)), tmp_path)
    assert grown.simulated == 2          # only the two seed-2 cells
    assert grown.cached == 4
    extra_sched = run_experiment(
        _small_spec(seeds=(0, 1, 2), schedulers=("proposed", "fair", "fifo")),
        tmp_path)
    assert extra_sched.simulated == 3    # only the fifo column
    assert extra_sched.cached == 6


def test_policy_specs_share_cache_with_string_schedulers(tmp_path):
    """The legacy alias: a default PolicySpec hits the cells a bare string
    scheduler wrote (and vice versa), while a parameter override is a new
    cell.  Records carry the canonical policy dict and the spec's label."""
    from repro.core.policies import PolicySpec
    first = run_experiment(_small_spec(seeds=(0,), schedulers=("fair",)),
                           tmp_path)
    assert first.simulated == 1
    as_spec = run_experiment(
        _small_spec(seeds=(0,), schedulers=(PolicySpec("fair"),)), tmp_path)
    assert as_spec.simulated == 0 and as_spec.cached == 1
    (rec,) = as_spec.records
    assert rec.scheduler == "fair"
    assert rec.policy == {"name": "fair", "params": {}}
    assert rec.policy_spec() == PolicySpec("fair")
    tweaked = run_experiment(
        _small_spec(seeds=(0,),
                    schedulers=(PolicySpec("fair", {"locality_delay": 2}),)),
        tmp_path)
    assert tweaked.simulated == 1        # parameter override = new cell
    (trec,) = tweaked.records
    assert trec.scheduler == "fair[locality_delay=2]"
    assert trec.policy == {"name": "fair", "params": {"locality_delay": 2}}


def test_unknown_and_duplicate_policies_rejected():
    with pytest.raises(ValueError, match="unknown"):
        _small_spec(schedulers=("warp_speed",))
    with pytest.raises(ValueError, match="duplicate"):
        from repro.core.policies import PolicySpec
        _small_spec(schedulers=("fair", PolicySpec("fair")))


def test_cache_distinguishes_cluster_and_trace(tmp_path):
    run_experiment(_small_spec(), tmp_path)
    other_cluster = ExperimentSpec(
        name="t",
        traces=(TraceRef(preset="mix_small", seed=0),),
        clusters=(ClusterSpec(num_machines=8, vms_per_machine=2,
                              replication=1),),
        schedulers=("proposed", "fair"), seeds=(0, 1))
    assert run_experiment(other_cluster, tmp_path).simulated == 4
    other_trace = _small_spec(trace_seed=9)
    assert run_experiment(other_trace, tmp_path).simulated == 4


def test_path_trace_cache_invalidates_on_edit(tmp_path):
    trace = generate_trace(PRESETS["mix_small"], seed=0)
    tpath = tmp_path / "trace.jsonl"
    trace.save(tpath)
    spec = ExperimentSpec(
        name="t", traces=(TraceRef(path=str(tpath)),),
        clusters=(ClusterSpec(num_machines=6, vms_per_machine=2,
                              replication=1),),
        schedulers=("fair",), seeds=(0,))
    cache = tmp_path / "cache"
    assert run_experiment(spec, cache).simulated == 1
    assert run_experiment(spec, cache).simulated == 0
    generate_trace(PRESETS["mix_small"], seed=1).save(tpath)   # edit the file
    assert run_experiment(spec, cache).simulated == 1


def test_records_survive_cache_round_trip(tmp_path):
    spec = _small_spec(seeds=(0,), schedulers=("proposed",))
    rec = run_experiment(spec, tmp_path).records[0]
    restored = RunRecord.from_dict(json.loads(json.dumps(rec.to_dict())))
    assert restored.to_dict() == rec.to_dict()
    assert restored.pair_key() == rec.pair_key()
    assert len(restored.jobs) == rec.jobs_total


def test_worker_pool_matches_inline(tmp_path):
    spec = _small_spec()
    inline = run_experiment(spec, tmp_path / "a")
    pooled = run_experiment(spec, tmp_path / "b", workers=2)
    assert pooled.simulated == 4

    def strip_wall(rec):
        d = rec.to_dict()
        d.pop("wall_time_s")            # measured timing, not sim output
        return d

    assert [strip_wall(r) for r in pooled.records] \
        == [strip_wall(r) for r in inline.records]


def test_pool_workers_stay_off_the_accelerator(monkeypatch):
    """Event-engine workers must never contend for the parent's chip: each
    spawn worker is pinned to the CPU backend whatever the parent's env."""
    import os
    from repro.experiments.runner import worker_pool
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with worker_pool(2) as pool:
        seen = pool.map(os.getenv, ["JAX_PLATFORMS"] * 2)
    assert seen == ["cpu", "cpu"]


def test_rows_trace_ref_resolves_and_caches(tmp_path):
    """The rows kind (hand-built mixes, e.g. the Fig.-2 grid) flows through
    the cache like any other trace and re-rolls placement per sim seed."""
    rows = (("sort", 2.0, 400.0, 0.0), ("grep", 1.0, 300.0, 10.0))
    ref = TraceRef(rows=rows, name="mini")
    t0, t1 = ref.resolve(0), ref.resolve(1)
    assert [j.job_id for j in t0.jobs] == ["mini-0000-sort", "mini-0001-grep"]
    assert t0.jobs[0].placement_seed != t1.jobs[0].placement_seed
    assert ref.descriptor()["kind"] == "rows"
    spec = ExperimentSpec(
        name="rows", traces=(ref,),
        clusters=(ClusterSpec(num_machines=4, vms_per_machine=2,
                              replication=1),),
        schedulers=("fair",), seeds=(0, 1))
    assert run_experiment(spec, tmp_path).simulated == 2
    assert run_experiment(spec, tmp_path).simulated == 0
    with pytest.raises(ValueError, match="exactly one of"):
        TraceRef(rows=rows, preset="mix_small")


def test_paired_runs_share_trace(tmp_path):
    """Both schedulers of one seed must see the identical job list."""
    report = run_experiment(_small_spec(seeds=(0,)), tmp_path)
    a, b = report.records
    assert a.pair_key() == b.pair_key()
    assert [j.job_id for j in a.jobs] == [j.job_id for j in b.jobs]
    assert [j.input_gb for j in a.jobs] == [j.input_gb for j in b.jobs]


# -- statistics -------------------------------------------------------------

def test_bootstrap_mean_ci_brackets_mean():
    vals = [1.0, 2.0, 3.0, 4.0, 5.0]
    mean, lo, hi = bootstrap_mean_ci(vals, n_boot=500, seed=1)
    assert mean == 3.0 and lo <= mean <= hi and lo < hi
    m1, l1, h1 = bootstrap_mean_ci(vals, n_boot=500, seed=1)
    assert (m1, l1, h1) == (mean, lo, hi)       # deterministic per seed


def test_paired_bootstrap_directionality():
    a = [100.0] * 6
    b = [110.0] * 6
    up = paired_bootstrap(a, b, higher_is_better=True)
    assert up.mean_gain_pct == pytest.approx(10.0)
    assert up.win_rate == 1.0
    down = paired_bootstrap(a, b, higher_is_better=False)
    assert down.mean_gain_pct == pytest.approx(-10.0)
    assert down.win_rate == 0.0
    with pytest.raises(ValueError):
        paired_bootstrap([1.0], [1.0, 2.0])


def test_paired_bootstrap_degenerate_pairs():
    # A scored zero throughput while B finished: a (capped) win for B
    up = paired_bootstrap([0.0, 100.0], [50.0, 100.0], higher_is_better=True)
    assert up.win_rate == 0.5 and up.mean_gain_pct == pytest.approx(50.0)
    # B left runs unfinished (inf completion time): a loss, not a tie
    down = paired_bootstrap([200.0, 200.0], [math.inf, 200.0],
                            higher_is_better=False)
    assert down.win_rate == 0.0 and down.mean_gain_pct == pytest.approx(-50.0)
    # both sides degenerate: a tie
    tie = paired_bootstrap([math.inf], [math.inf], higher_is_better=False)
    assert tie.mean_gain_pct == 0.0


def test_compare_requires_common_cells(tmp_path):
    report = run_experiment(_small_spec(seeds=(0, 1)), tmp_path)
    by = report.by_scheduler()
    cmp = compare_throughput(by["fair"], by["proposed"])
    assert cmp.n_pairs == 2
    assert math.isfinite(cmp.mean_gain_pct)
    per_w = compare_completion_by_workload(by["fair"], by["proposed"])
    assert per_w and all(c.n_pairs >= 1 for c in per_w.values())
    with pytest.raises(ValueError, match="no common"):
        compare_throughput(by["fair"][:1], by["proposed"][1:])


# -- the paper reproduction -------------------------------------------------

def test_paper_quick_reports_ci(tmp_path):
    report = run_paper(QUICK_SEEDS, cache_dir=tmp_path)
    assert report.throughput.n_pairs == len(QUICK_SEEDS)
    assert report.throughput.ci_lo_pct <= report.throughput.mean_gain_pct \
        <= report.throughput.ci_hi_pct
    assert set(report.per_workload) == {"grep", "wordcount", "sort",
                                        "permutation", "inverted_index"}
    text = report.format()
    assert "95% CI" in text and "weakest-gain workload" in text
    # quick rerun is served from cache
    again = run_paper(QUICK_SEEDS, cache_dir=tmp_path)
    assert again.simulated == 0 and again.cached == 2 * len(QUICK_SEEDS)


def test_paper_full_reproduces_claims(tmp_path):
    """The headline acceptance check: positive throughput gain over Fair
    with a CI excluding zero, and Permutation as the weakest-gain workload
    (Fig. 3 ordering)."""
    report = run_paper(FULL_SEEDS, cache_dir=tmp_path)
    assert report.failures() == []
    assert report.throughput.mean_gain_pct > 0
    assert report.throughput.ci_lo_pct > 0
    assert report.weakest_workload() == "permutation"
    # every workload except permutation gains under the proposed scheduler
    for w, cmp in report.per_workload.items():
        if w != "permutation":
            assert cmp.mean_gain_pct > 0, (w, cmp.mean_gain_pct)


# -- surrogate cache namespace ----------------------------------------------

def test_surrogate_namespace_disjoint_from_event_cache(tmp_path):
    """A surrogate sweep into a warm event cache neither serves from nor
    touches the event engine's cells — the engine-id descriptor key forks
    the hash family, so the two engines coexist in one cache dir."""
    from repro.experiments.surrogate import run_surrogate, surrogate_hash

    spec = _small_spec()
    event = run_experiment(spec, tmp_path)
    assert event.simulated == 4
    before = {p: p.read_bytes() for p in sorted(tmp_path.rglob("*.json"))}
    sur = run_surrogate(spec, tmp_path)
    assert sur.simulated == 4 and sur.cached == 0   # no cross-engine hits
    for path, blob in before.items():
        assert path.read_bytes() == blob            # event cells untouched
    # and back: the event engine still sees its own cells, nothing more
    again = run_experiment(spec, tmp_path)
    assert again.simulated == 0 and again.cached == 4
    for cell in spec.cells():
        assert surrogate_hash(cell) != cell.cache_hash()
