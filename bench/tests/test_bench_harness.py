"""The harness on the CPU: found by name, driven end to end, and shown to
call a broken timed path incorrect."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from conftest import BENCH, GRID_CELL, ROOT, TINY_CELL
from harness import check, jobtypes, program, registry, runner
from harness.traffic import Stream

SEED = 2**31 + 12345


def _run(root, trace=False, seed=SEED, cell=TINY_CELL):
    return runner.run(cell, seed, 0.3, trace, time.perf_counter(),
                      root=root)


def test_cell_found_by_name_without_edits(bench_root):
    cell = registry.find_cell(TINY_CELL, bench_root)
    assert cell.config_name == "tiny_20x2"
    assert cell.config["num_jobs"] == 12
    assert cell.traffic_name == "tiny"
    assert [m.name for m in cell.end_to_end] == ["query_p50_s", "setup_s"]
    assert [m.name for m in cell.per_layer] == ["tiny_requests"]


def test_real_cells_found():
    bench = registry.load_benchmark(ROOT)
    for w in bench["workloads"]:
        cell = registry.find_cell(w["name"], ROOT)
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m.name for m in cell.end_to_end]


def test_fixture_run_is_correct(bench_root):
    result = _run(bench_root)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"query_p50_s", "setup_s"}
    assert list(result)[-1] == "compared"


def test_traced_fixture_run_reads_the_new_metric(bench_root):
    result = _run(bench_root, trace=True)
    assert result["correct"], result["compared"]
    assert result["metrics"]["tiny_requests"]["value"] == 1.0
    assert result["device"]["window_s"] > 0


def _state_unchanged(monkeypatch):
    """Every integrator step hands back the state it was given."""
    import jax
    from repro.simcluster import surrogate
    monkeypatch.setattr(surrogate, "_KERNEL_CACHE", {})
    monkeypatch.setattr(jax.lax, "scan",
                        lambda f, init, xs=None, **kw: (init, None))


def _half_batch(monkeypatch):
    """Only the first half of a batch is integrated; the cells left out
    carry the kept cells' answers."""
    from repro.experiments import surrogate as front
    real = front.run_batch

    def half(inputs, **kw):
        n = max(1, len(inputs) // 2)
        kept = real(inputs[:n], **kw)
        return kept + [kept[n - 1 - (i % n)] for i in range(len(inputs) - n)]

    monkeypatch.setattr(front, "run_batch", half)


def _answer_altered(monkeypatch):
    """Finish times come out of the kernel one step late."""
    from repro.simcluster import surrogate
    real = surrogate._unpack_result

    def late(cell, out):
        out = dict(out)
        f = np.asarray(out["finish"])
        out["finish"] = np.where(f < surrogate._INF, f + surrogate.DT, f)
        return real(cell, out)

    monkeypatch.setattr(surrogate, "_unpack_result", late)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered])
def test_broken_timed_path_is_not_correct(bench_root, monkeypatch, fault):
    fault(monkeypatch)
    result = _run(bench_root)
    assert result["correct"] is False, result["compared"]


def test_grid_check_rule_run_is_correct(bench_root):
    """The grid's traffic file and check rule, on a tiny job-type
    deployment: the program agrees with the reference."""
    result = _run(bench_root, cell=GRID_CELL)
    assert result["correct"], result["compared"]


@pytest.mark.parametrize("seed", [SEED, SEED + 1])
def test_grid_check_rule_catches_half_batch(bench_root, monkeypatch, seed):
    """At the grid's own check rule (one cell from each quarter of every
    request's batch), half a batch left out is caught in every run, not
    by the luck of the draw."""
    _half_batch(monkeypatch)
    result = _run(bench_root, cell=GRID_CELL, seed=seed)
    assert result["correct"] is False, result["compared"]
    assert result["compared"]["mismatched_cells"]["value"] >= 1


def test_check_sample_covers_both_halves_of_every_request():
    traffic = json.loads((BENCH / "traffic" / "grid.json").read_text())
    config = json.loads((BENCH / "configs" / "fb2009_600x2.json").read_text())
    stream = Stream(traffic, "grid.fb2009_600x2", SEED)
    groups, n = [], 0
    for k in range(4):
        size = len(program.request_cells(config, stream.request(k)))
        groups.append(list(range(n, n + size)))
        n += size
    for seed in range(20):
        picked = set(check.sample(groups, [0.0] * n, traffic,
                                  "grid.fb2009_600x2", seed))
        for g in groups:
            half = len(g) // 2
            assert picked & set(g[:half]) and picked & set(g[half:])


def test_job_type_seeds_share_jobs_and_gaps():
    """Every seed draws the same jobs and the same arrival gaps, in another
    order, so a seed never changes the work."""
    config = json.loads((BENCH / "configs" / "fb2009_600x2.json").read_text())
    trace = program.trace_recipe(config)
    from harness.reference import deadline
    a = jobtypes.rows(trace, 2**31 + 7, deadline)
    b = jobtypes.rows(trace, 2**31 + 8, deadline)
    assert a != b
    assert sorted(r[:3] for r in a) == sorted(r[:3] for r in b)
    assert abs(a[-1][3] - b[-1][3]) < 0.01
    counts = jobtypes.apportion([t["jobs"] for t in trace["types"]], 1000)
    assert counts == [958, 33, 2, 1, 0, 5, 0, 0, 1, 0]


def test_cluster_keys_all_reach_the_program():
    config = json.loads((BENCH / "configs" / "paper_20x2.json").read_text())
    config["cluster"]["overload_pending_factor"] = 0.4
    spec = program.cluster_spec(config)
    assert spec.adaptive.overload_pending_factor == 0.4
    assert spec.num_machines == config["cluster"]["num_machines"]
    config["cluster"]["rack_count"] = 4
    with pytest.raises(ValueError, match="rack_count"):
        program.cluster_spec(config)


def test_cli_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "query.paper_20x2", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert not any(line.startswith("{")
                   for line in proc.stdout.splitlines())
    assert "no accelerator" in proc.stderr


def test_benchmark_json_names_existing_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH / "checks" / f"{w['name']}.json").is_file()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
