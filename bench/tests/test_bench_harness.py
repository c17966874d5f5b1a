"""The harness on the CPU: found by name, driven end to end, and shown to
call a broken timed path incorrect."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import control
from conftest import (BENCH, GRID_CELL, KEYED_CELL, KEYED_REFERENCE,
                      PROBE_CELL, PROBE_EXTRA, ROOT, TINY_CELL)
from harness import check, jobtypes, program, reference, registry, runner
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


def test_real_cells_found(benchmark_root):
    """Every cell of the benchmark, and of the benchmark with a deployment
    added as new files and entries, is found with its metrics and with the
    reference its configuration names (the default where it names none)."""
    bench = registry.load_benchmark(benchmark_root)
    for w in bench["workloads"]:
        cell = registry.find_cell(w["name"], benchmark_root)
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m.name for m in cell.end_to_end]
        named = cell.config.get("reference")
        if named is None:
            assert cell.reference is reference
        else:
            assert (Path(cell.reference.__file__).resolve()
                    == (benchmark_root / named).resolve())
            assert all(hasattr(cell.reference, a)
                       for a in registry.REFERENCE_API)


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
    a = jobtypes.rows(trace, 2**31 + 7, reference.deadline)
    b = jobtypes.rows(trace, 2**31 + 8, reference.deadline)
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


def test_cluster_keys_a_reference_declares_reach_the_program():
    """A key the reference models goes to the program, nested groups to
    their config classes; a key the program does not take is refused even
    where the reference declares it."""
    from repro.core.types import FaultConfig
    config = json.loads((BENCH / "configs" / "paper_20x2.json").read_text())
    config["cluster"]["faults"] = {"enabled": True}
    keys = reference.CLUSTER_KEYS + ("faults",)
    spec = program.cluster_spec(config, keys)
    assert spec.faults == FaultConfig(enabled=True)
    assert spec.adaptive.overload_active_factor == 0.5
    # a key no program takes: a rack key would stop being one once a
    # deployment with racks brings it to ClusterSpec
    config["cluster"]["not_a_cluster_key"] = 4
    with pytest.raises(ValueError,
                       match="program does not take.*not_a_cluster_key"):
        program.cluster_spec(config, keys + ("not_a_cluster_key",))


def test_keyed_cell_found_with_its_reference(bench_root):
    """A configuration that names a reference gets that reference, with the
    cluster keys it models; the others keep the default one."""
    cell = registry.find_cell(KEYED_CELL, bench_root)
    assert cell.reference is not reference
    assert cell.reference.__file__ == str(bench_root / KEYED_REFERENCE)
    assert set(cell.reference.CLUSTER_KEYS) == (
        set(reference.CLUSTER_KEYS) | {"faults"})
    assert registry.find_cell(TINY_CELL, bench_root).reference is reference
    exp = program.spec(cell, Stream(cell.traffic, KEYED_CELL, SEED).request(0))
    assert exp.clusters[0].faults.enabled


def test_keyed_cell_run_is_correct(bench_root):
    """The deployment with a key of its own, added as new files only, runs
    end to end and its check passes."""
    result = _run(bench_root, cell=KEYED_CELL)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 1


def test_control_runs_on_keyed_cell(bench_root):
    numbers, limits = control.control_numbers(KEYED_CELL, SEED, 1,
                                              root=bench_root)
    assert set(numbers) == set(check.NUMBERS) == set(limits)
    assert all(np.isfinite(v) for v in numbers.values())


def test_extended_cell_run_is_correct(extended_root):
    """The deployment added to the real benchmark as new files and entries
    runs end to end on the CPU, reports the grid's metrics, and its check
    compares the number its reference adds."""
    result = _run(extended_root, cell=PROBE_CELL)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"cells_per_s", "setup_s"}
    assert tuple(result["compared"]) == check.names(PROBE_EXTRA)
    assert result["compared"]["makespan_max_abs"]["limit"] == 30


def test_extended_cell_broken_extra_number_is_not_correct(extended_root,
                                                          monkeypatch):
    """The program's makespan read a minute late is caught by the extra
    number alone: every other number still agrees."""
    find = registry.find_cell

    def broken(workload, root):
        cell = find(workload, root)
        read = cell.reference.record_numbers
        monkeypatch.setattr(
            cell.reference, "record_numbers",
            lambda rec: {k: v + 60.0 for k, v in read(rec).items()})
        return cell

    monkeypatch.setattr(registry, "find_cell", broken)
    result = _run(extended_root, cell=PROBE_CELL)
    assert result["correct"] is False
    compared = result["compared"]
    assert (compared["makespan_max_abs"]["value"]
            > compared["makespan_max_abs"]["limit"])
    assert all(e["value"] <= e["limit"] for k, e in compared.items()
               if k != "makespan_max_abs"), compared


def test_extra_number_needs_its_limit(extended_root):
    """A checks file without a limit for the reference's extra number is
    refused before any request is served."""
    path = extended_root / "bench" / "checks" / f"{PROBE_CELL}.json"
    checks = json.loads(path.read_text())
    del checks["limits"]["makespan_max_abs"]
    path.write_text(json.dumps(checks))
    with pytest.raises(KeyError, match="makespan_max_abs"):
        _run(extended_root, cell=PROBE_CELL)


def test_control_compares_extra_numbers(extended_root):
    numbers, limits = control.control_numbers(PROBE_CELL, SEED, 1,
                                              root=extended_root)
    assert tuple(numbers) == tuple(limits) == check.names(PROBE_EXTRA)
    assert all(np.isfinite(v) for v in numbers.values())


@pytest.mark.parametrize("gap, correct", [(0.0, True), (30.0, True),
                                          (30.5, False), (np.nan, False)])
def test_extra_number_gap_is_compared(gap, correct):
    """An extra number's gap is its largest over the cells, beside the
    default numbers, and a NaN gap is never correct."""
    ref = {"job_ids": ["a"], "finish": np.array([6.0]),
           "locality_rate": 0.5, "makespan": 6.0}
    got = dict(ref, makespan=6.0 + gap)
    numbers = check.compare([ref, got], [ref, ref], PROBE_EXTRA)
    assert tuple(numbers) == check.names(PROBE_EXTRA)
    assert numbers["mismatched_cells"] == 0
    limits = dict.fromkeys(check.NUMBERS, 0.0)
    limits["makespan_max_abs"] = 30.0
    assert check.verdict(numbers, limits) is correct
    assert check.names() == check.NUMBERS


def test_keyed_cell_without_its_reference_is_refused(bench_root):
    """The same configuration under the default reference, which does not
    model its fault layer, is refused before any request is served."""
    path = bench_root / "bench" / "configs" / "keyed_20x2.json"
    config = json.loads(path.read_text())
    del config["reference"]
    path.write_text(json.dumps(config))
    assert registry.find_cell(KEYED_CELL, bench_root).reference is reference
    with pytest.raises(ValueError, match="faults"):
        _run(bench_root, cell=KEYED_CELL)


@pytest.mark.parametrize("named, text, error, match", [
    (KEYED_REFERENCE,
     "from harness.reference import CLUSTER_KEYS, build, deadline, lower\n",
     AttributeError, r"keyed\.py.*answer"),
    ("bench/references/keyed.txt", "", ValueError, "keyed.txt"),
    ("src/keyed.py", "", ValueError, "src/keyed.py"),
    ("bench/../keyed.py", "", ValueError, "keyed.py"),
    ("bench/references/absent.py", "", FileNotFoundError, "absent.py"),
    (KEYED_REFERENCE,
     "from harness.reference import *\nEXTRA_NUMBERS = ('makespan',)\n",
     AttributeError, r"keyed\.py.*record_numbers"),
])
def test_reference_file_is_checked(bench_root, named, text, error, match):
    """A reference that lacks part of the interface, or that is not a
    Python file under ``bench/``, is refused with its name."""
    (bench_root / KEYED_REFERENCE).write_text(text)
    path = bench_root / "bench" / "configs" / "keyed_20x2.json"
    config = json.loads(path.read_text())
    config["reference"] = named
    path.write_text(json.dumps(config))
    with pytest.raises(error, match=match):
        registry.find_cell(KEYED_CELL, bench_root)


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


def test_benchmark_json_names_existing_files(benchmark_root):
    bench = json.loads((benchmark_root / "BENCHMARK.json").read_text())
    files = benchmark_root / "bench"
    for c in bench["configs"]:
        assert (benchmark_root / c["file"]).is_file()
    for w in bench["workloads"]:
        assert (files / "traffic" / f"{w['traffic']}.json").is_file()
        assert (files / "checks" / f"{w['name']}.json").is_file()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (files / "metrics" / f"{m['name']}.py").is_file()
