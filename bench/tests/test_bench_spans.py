"""The program's spans in a trace: what a request opens, what the new
readers make of them, and that the readers already there read the same."""
import json
import shutil
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from conftest import BENCH, ROOT, TINY_CELL
from harness import registry, runner, spans, xtrace
from harness.traffic import Request

FIXTURE = Path(__file__).parent / "fixtures" / "query_v5e.xplane.pb.gz"

EXISTING = ("build_ms_per_cell.sweep", "engine_host_ms_per_cell.sweep",
            "kernel_ns_per_job_step.sweep", "kernel_roofline.sweep",
            "device_idle_share.sweep", "build_ms_per_query.query",
            "engine_host_ms_per_query.query", "kernel_ms_per_query.query",
            "device_idle_share.query")
NEW = ("job_specs_ms_per_cell.sweep", "engine_records_ms_per_cell.sweep",
       "kernel_lane_step_use.sweep", "kernel_ring_ns_per_job_step.sweep",
       "engine_dispatch_ms_per_query.query",
       "engine_records_ms_per_query.query", "kernel_us_per_step_run.query")
#: parent -> the spans directly inside it, as the program opens them
NESTING = {
    spans.SWEEP: (spans.CACHE_LOOKUP, spans.BUILD, spans.RUN_BATCH,
                  spans.RECORDS),
    spans.BUILD: (spans.RESOLVE, spans.BUILD_CELL),
    spans.BUILD_CELL: (spans.JOB_SPECS,),
    spans.RUN_BATCH: (spans.PACK, spans.DISPATCH, spans.FETCH, spans.UNPACK),
}


def _reader(name):
    return registry._reader(ROOT, name)


def _ctx(trace, window, served):
    return runner.Context(setup_s=1.0, served=served, window_s=window[1]
                          - window[0], device_kind="TPU v5 lite",
                          trace=trace, traced_window=window)


def _served(n_requests, cells_each, finish_times):
    """Requests of ``cells_each`` cells whose records finish their jobs at
    ``finish_times``."""
    jobs = [SimpleNamespace(finish_time=t) for t in finish_times]
    rec = SimpleNamespace(jobs_total=len(jobs), jobs=jobs)
    return [runner.Served(Request(k, ({"name": "fair"},) * cells_each, (0,)),
                          float(k), float(k) + 0.5, True,
                          [rec] * cells_each)
            for k in range(n_requests)]


@pytest.fixture(scope="module")
def request_trace(tmp_path_factory):
    """One tiny ``run_surrogate`` request profiled on the CPU."""
    import jax
    from repro.core.types import ClusterSpec
    from repro.experiments.runner import ExperimentSpec, TraceRef
    from repro.experiments.surrogate import run_surrogate
    spec = ExperimentSpec(
        name="spans", traces=(TraceRef(preset="mix_small"),),
        clusters=(ClusterSpec(num_machines=6, vms_per_machine=2,
                              replication=1),),
        schedulers=("fair", "proposed"), seeds=(0, 1))
    tmp = tmp_path_factory.mktemp("spans")
    with jax.profiler.trace(str(tmp / "trace")):
        report = run_surrogate(spec, tmp / "records")
    assert report.simulated == 4
    return spans.reduce_file(xtrace.latest_xplane(str(tmp / "trace")))


def test_request_opens_every_span_nested_as_listed(request_trace):
    t = request_trace
    names = {spans.SWEEP} | {n for kids in NESTING.values() for n in kids}
    assert set(t.spans) == names
    assert len(t.spans[spans.SWEEP]) == 1
    for parent, kids in NESTING.items():
        for kid in kids:
            for a, b in t.spans[kid]:
                assert any(x <= a and b <= y for x, y in t.spans[parent]), \
                    (kid, parent)
    requests = {args["request"] for name in names for args in t.args[name]}
    assert len(requests) == 1
    (shape,) = t.args[spans.DISPATCH]
    (unpack,) = t.args[spans.UNPACK]
    assert unpack["lanes"] == shape["lanes"] == 4
    assert unpack["jobs"] == shape["jobs"]
    assert 0 < unpack["steps_run"] <= shape["steps"]
    assert unpack["steps_run"] % 256 == 0
    assert unpack["steps_run"] <= unpack["lane_steps_run"] \
        <= unpack["lanes"] * unpack["steps_run"]
    sweep = t.span_seconds(spans.SWEEP)
    assert 0.0 <= t.self_seconds(spans.SWEEP) < sweep
    assert t.self_seconds(spans.JOB_SPECS) == pytest.approx(
        t.span_seconds(spans.JOB_SPECS))


def test_self_seconds_leave_out_what_children_cover():
    t = spans.Traced(spans={"p": [(0.0, 10.0), (20.0, 22.0)],
                            "c": [(1.0, 3.0), (3.0, 4.0), (20.5, 21.0)],
                            "g": [(1.5, 2.5)], "out": [(9.0, 12.0)]})
    assert t.self_seconds("p") == pytest.approx(12.0 - 3.0 - 0.5)
    assert t.self_seconds("c") == pytest.approx(2.0 - 1.0 + 1.0 + 0.5)


@pytest.fixture
def synthetic(monkeypatch):
    """Two queries of two cells each, every record 3 jobs finishing at
    60, 1230 and 600 s (3 x 205 job-steps), one executable shape."""
    shape = {"lanes": 2, "jobs": 8, "steps": 512}
    t = spans.Traced(
        busy={"/device:TPU:0": [(2.2, 2.8), (6.4, 7.0)]},
        op_seconds={"%fusion.1": 0.5, "%fusion.2": 0.3, "%copy.3": 0.2,
                    "%while.4": 0.2, "%other.5": 0.1},
        modules=[("jit_kernel(1)", 2.2, 2.8), ("jit_kernel(1)", 6.4, 7.0)],
        spans={"bench.window": [(0.0, 10.0)],
               spans.JOB_SPECS: [(1.0, 1.5), (5.0, 5.25)],
               spans.PACK: [(2.0, 2.1), (6.0, 6.2)],
               spans.DISPATCH: [(2.1, 2.15), (6.2, 6.3)],
               spans.UNPACK: [(3.0, 3.2), (7.0, 7.1)],
               spans.RECORDS: [(3.2, 3.4), (7.1, 7.2)]},
        args={spans.JOB_SPECS: [{"request": 1}, {"request": 2}],
              spans.PACK: [{"request": 1}, {"request": 2}],
              spans.DISPATCH: [dict(shape, request=1),
                               dict(shape, request=2)],
              spans.UNPACK: [{"lanes": 2, "jobs": 8, "steps_run": 256,
                              "lane_steps_run": 512, "request": 1},
                             {"lanes": 2, "jobs": 8, "steps_run": 512,
                              "lane_steps_run": 768, "request": 2}],
              spans.RECORDS: [{"request": 1}, {"request": 2}]})
    asked = []

    def stage_map(lanes, jobs, steps):
        asked.append((lanes, jobs, steps))
        return {"fusion.1": "ring_drain", "fusion.2": "ring_scatter",
                "copy.3": "map_alloc", "while.4": "unscoped"}

    monkeypatch.setattr(spans, "stage_map", stage_map)
    ctx = _ctx(t, (0.0, 10.0), _served(2, 2, [60.0, 1230.0, 600.0]))
    return ctx, asked


@pytest.mark.parametrize("name,expected", [
    ("job_specs_ms_per_cell.sweep", 0.75 / 4 * 1e3),
    ("engine_records_ms_per_cell.sweep", (0.3 + 0.3) / 4 * 1e3),
    ("kernel_lane_step_use.sweep", 4 * 615 / (2 * 8 * 256 + 2 * 8 * 512)),
    ("kernel_ring_ns_per_job_step.sweep", (0.5 + 0.3) / (4 * 615) * 1e9),
    ("engine_dispatch_ms_per_query.query", (0.3 + 0.15) / 2 * 1e3),
    ("engine_records_ms_per_query.query", (0.3 + 0.3) / 2 * 1e3),
    ("kernel_us_per_step_run.query", 1.2 / (256 + 512) * 1e6),
])
def test_new_reader_on_a_synthetic_trace(synthetic, name, expected):
    ctx, asked = synthetic
    assert _reader(name)(ctx) == pytest.approx(expected, rel=1e-12)
    if name == "kernel_ring_ns_per_job_step.sweep":
        assert asked == [(2, 8, 512)]


def test_ring_reader_needs_one_executable_shape(synthetic):
    ctx, _ = synthetic
    ctx.trace.args[spans.DISPATCH][1]["lanes"] = 1
    assert _reader("kernel_ring_ns_per_job_step.sweep")(ctx) is None


def test_stage_map_is_none_for_a_program_without_one(monkeypatch):
    from repro.simcluster import surrogate
    monkeypatch.delattr(surrogate, "kernel_stages")
    assert spans.stage_map(2, 8, 256) is None


def _fixture_ctx(trace):
    return _ctx(trace, trace.window("bench.window"),
                _served(3, 4, [60.0, 1230.0, 600.0]))


def test_readers_already_there_read_the_same_on_the_recorded_trace():
    """The trace of a program without spans of its own (three queries on a
    v5e): the nine readers read what they read from the benchmark's own
    reduction, and the new readers find nothing."""
    old = spans._base_reduce_file(str(FIXTURE))
    new = spans.reduce_file(str(FIXTURE))
    assert type(old) is xtrace.Reduced and isinstance(new, spans.Traced)
    assert new.args == {} and new.spans == old.spans
    for name in EXISTING:
        before = _reader(name)(_fixture_ctx(old))
        assert before is not None, name
        assert _reader(name)(_fixture_ctx(new)) == before, name
    for name in NEW:
        assert _reader(name)(_fixture_ctx(new)) is None, name
    assert new.idle_gaps(new.window("bench.window")) \
        == old.idle_gaps(old.window("bench.window"))


def test_install_makes_the_reduction_keep_program_spans():
    spans.install()
    spans.install()
    assert xtrace.reduce_file is spans.reduce_file
    assert spans._base_reduce_file is not spans.reduce_file


def test_traced_fixture_run_reads_the_program_span_metrics(bench_root):
    """A traced run on the CPU: every metric read from the program's spans
    reads a number; those read from the device trace find none here."""
    for name in NEW:
        shutil.copy(BENCH / "metrics" / f"{name}.py",
                    bench_root / "bench" / "metrics")
    path = bench_root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["per_layer"] += [
        {"name": name, "unit": "x", "better": "lower",
         "source": "program_span", "layer": "fixture",
         "moves": "query_p50_s", "workloads": [TINY_CELL]}
        for name in NEW]
    path.write_text(json.dumps(bench))
    result = runner.run(TINY_CELL, 2**31 + 99, 0.3, True,
                        time.perf_counter(), root=bench_root)
    assert result["correct"], result["compared"]
    got = result["metrics"]
    for name in ("job_specs_ms_per_cell.sweep",
                 "engine_records_ms_per_cell.sweep",
                 "engine_dispatch_ms_per_query.query",
                 "engine_records_ms_per_query.query"):
        assert got[name]["value"] > 0, name
    assert 0 < got["kernel_lane_step_use.sweep"]["value"] <= 1
    assert "kernel_ring_ns_per_job_step.sweep" not in got
    assert "kernel_us_per_step_run.query" not in got
