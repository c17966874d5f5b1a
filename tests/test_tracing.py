"""Decision-trace bus: config plumbing, observer bit-exactness, typed
fault records, event vocabulary, exporters, warehouse integration, and
the CLI discovery verbs.

The bus is default-off and a pure observer: enabling it draws from no RNG
and changes no decision — a traced run must be bit-identical to the
untraced run — and ``tracing`` never enters ``ClusterSpec.to_dict()``
(even enabled), so a traced replay of a cached cell hashes onto the same
cache entry it explains.
"""
import dataclasses
import json
import random

import pytest

from repro.core.policies import PolicySpec
from repro.core.tracing import (EVENT_KINDS, LATCH_RELEASE_CAUSES,
                                PARK_GATES, FaultEvent, TraceBus,
                                dumps_canonical)
from repro.core.types import ClusterSpec, FaultConfig, TraceConfig
from repro.simcluster.largescale import run_scenario
from repro.simcluster.sim import ClusterSim
from repro.simcluster.workloads import default_deadline, make_job

TRACE_ON = TraceConfig(enabled=True, pressure_every=5.0)
CHURN = FaultConfig(enabled=True, crash_mtbf=300.0, crash_mttr=60.0,
                    rereplicate_after=30.0)


def _spec(machines=6, vms=2, replication=1, tracing=TraceConfig(),
          faults=FaultConfig()):
    return ClusterSpec(num_machines=machines, vms_per_machine=vms,
                       replication=replication, tracing=tracing,
                       faults=faults)


def _jobs(spec, n=8, seed=0, stagger=10.0):
    rng = random.Random(seed)
    jobs = []
    for i in range(n):
        w = ["wordcount", "grep", "sort"][i % 3]
        gb = 0.5 + 0.5 * (i % 4)
        jobs.append(make_job(f"{w}-{i}", w, gb, default_deadline(w, gb),
                             spec, rng, submit_time=stagger * i))
    return jobs


def _run(spec, policy="proposed", seed=0, jobs=None):
    sched = PolicySpec(policy).build(spec)
    sim = ClusterSim(spec, sched, seed=seed)
    res = sim.run(jobs if jobs is not None else _jobs(spec))
    return sim, res


# -- config plumbing ----------------------------------------------------------

def test_trace_config_validation_and_roundtrip():
    assert TraceConfig().enabled is False
    with pytest.raises(ValueError):
        TraceConfig(pressure_every=-1.0)
    with pytest.raises(ValueError):
        TraceConfig(max_events=-1)
    rt = TraceConfig.from_dict(TRACE_ON.to_dict())
    assert rt == TRACE_ON


def test_tracing_always_omitted_from_spec_dict():
    """Cache-hash stability, stronger than the faults rule: tracing is a
    pure observer, so even an *enabled* config is dropped from the dict —
    a traced replay must hash onto the cell it explains."""
    assert "tracing" not in ClusterSpec(num_machines=4,
                                        vms_per_machine=2).to_dict()
    assert "tracing" not in _spec(tracing=TRACE_ON).to_dict()
    # explicit tracing in an incoming dict still deserializes
    d = _spec().to_dict()
    d["tracing"] = TRACE_ON.to_dict()
    assert ClusterSpec.from_dict(d).tracing == TRACE_ON


def test_no_bus_attached_while_disabled():
    sim, res = _run(_spec())
    assert sim.trace is None and res.trace is None


# -- observer bit-exactness ---------------------------------------------------

@pytest.mark.parametrize("policy", ["proposed", "adaptive", "fair"])
def test_traced_run_is_bit_exact(policy):
    """Tracing draws from no RNG: the traced run reproduces the untraced
    run decision-for-decision (makespan, per-job finish times, locality
    split), it just also carries the bus."""
    base = _spec()
    _, res_off = _run(base, policy=policy, seed=3)
    _, res_on = _run(_spec(tracing=TRACE_ON), policy=policy, seed=3,
                     jobs=_jobs(base))
    assert res_on.trace is not None and res_on.trace.total > 0
    assert res_on.makespan == res_off.makespan
    assert res_on.locality_rate() == res_off.locality_rate()
    assert res_on.speculative_launches == res_off.speculative_launches
    assert {j: r.finish_time for j, r in res_on.jobs.items()} \
        == {j: r.finish_time for j, r in res_off.jobs.items()}


def test_traced_churn_run_is_byte_reproducible():
    """Same (config, seed): two traced churn runs produce the identical
    fault log and the byte-identical JSONL bus serialization."""
    spec = _spec(tracing=TRACE_ON, faults=CHURN)
    sim_a, res_a = _run(spec, policy="adaptive", seed=7)
    sim_b, res_b = _run(spec, policy="adaptive", seed=7)
    assert sim_a.fault_stats["crashes"] > 0
    assert sim_a.fault_log == sim_b.fault_log
    assert res_a.trace.to_jsonl() == res_b.trace.to_jsonl()


# -- typed fault records ------------------------------------------------------

def test_fault_event_is_byte_compatible_with_tuples():
    """FaultEvent named tuples serialize, compare and unpack exactly like
    the bare (time, kind, machine) tuples they replaced — the
    byte-reproducibility pins in tests/test_faults.py hold unchanged."""
    ev = FaultEvent(12.5, "crash", 3)
    assert json.dumps([ev]) == json.dumps([(12.5, "crash", 3)])
    assert ev == (12.5, "crash", 3)
    t, kind, machine = ev
    assert (t, kind, machine) == (12.5, "crash", 3)
    assert ev.time == 12.5 and ev.kind == "crash" and ev.machine == 3
    sim, _ = _run(_spec(faults=CHURN), seed=7)
    assert sim.fault_stats["crashes"] > 0
    assert all(isinstance(e, FaultEvent) for e in sim.fault_log)
    assert json.dumps(sim.fault_log) \
        == json.dumps([tuple(e) for e in sim.fault_log])


def test_fault_bus_events_match_fault_log():
    sim, res = _run(_spec(tracing=TRACE_ON, faults=CHURN), policy="adaptive",
                    seed=7)
    bus = res.trace
    for kind in ("crash", "restart", "rereplicate"):
        assert bus.count(kind) == sum(1 for e in sim.fault_log
                                      if e.kind == kind)


# -- event vocabulary ---------------------------------------------------------

def test_emitted_kinds_are_registered():
    _, res = _run(_spec(tracing=TRACE_ON, faults=CHURN), policy="adaptive",
                  seed=7)
    registered = {k for kinds in EVENT_KINDS.values() for k in kinds}
    assert set(res.trace.counts) <= registered


def test_park_deny_gates_are_named():
    """Every park_deny event names its failing gate from the PARK_GATES
    vocabulary, with the gate's own signals alongside."""
    gates = set()
    for policy in ("proposed", "adaptive"):
        _, res = _run(_spec(tracing=TRACE_ON), policy=policy, seed=3,
                      jobs=_jobs(_spec(), n=12, stagger=2.0))
        for _, kind, data in res.trace.events:
            if kind == "park_deny":
                gates.add(data["gate"])
    assert gates and gates <= set(PARK_GATES)
    assert len(gates) >= 2


def test_latch_trip_and_release_events():
    """An overloaded adaptive run emits latch_trip with the triggering
    counters, and every latch_release names its cause."""
    spec = _spec(machines=4, tracing=TRACE_ON)
    jobs = _jobs(spec, n=12, stagger=0.5)
    # a straggler job arriving after the burst drains: the latch (if still
    # set) must release on the empty cluster rather than throttle it
    jobs += [make_job("late-0", "grep", 0.5,
                      default_deadline("grep", 0.5), spec,
                      random.Random(99), submit_time=20_000.0)]
    _, res = _run(spec, policy="adaptive", seed=1, jobs=jobs)
    bus = res.trace
    assert bus.count("latch_trip") > 0
    trips = [d for _, k, d in bus.events if k == "latch_trip"]
    for d in trips:
        assert d["pending_maps"] >= d["pending_bar"]
        assert d["crowd"] >= d["crowd_bar"]
    releases = [d for _, k, d in bus.events if k == "latch_release"]
    assert len(releases) > 0
    for d in releases:
        assert d["cause"] in LATCH_RELEASE_CAUSES


def test_category_switches_gate_emission():
    """Per-category booleans suppress exactly their kinds."""
    spec = _spec(tracing=TraceConfig(enabled=True, launches=False))
    _, res = _run(spec, policy="adaptive", seed=3, jobs=_jobs(spec))
    bus = res.trace
    for kind in EVENT_KINDS["launches"]:
        assert bus.count(kind) == 0
    assert any(bus.count(k) for k in EVENT_KINDS["parks"])


def test_max_events_cap_bounds_memory_not_counts():
    spec = _spec(tracing=TraceConfig(enabled=True, max_events=25))
    _, res = _run(spec, policy="adaptive", seed=3, jobs=_jobs(spec))
    bus = res.trace
    assert len(bus.events) == 25
    assert bus.dropped > 0
    assert bus.total == len(bus.events) + bus.dropped
    assert sum(bus.counts.values()) == bus.total


# -- scenario suite + exporters -----------------------------------------------

def test_run_scenario_tracing_hook(tmp_path):
    from repro.experiments.telemetry import (fold_trace, write_chrome_trace,
                                             write_jsonl)
    res = run_scenario("smoke_40x2", scheduler="adaptive", seed=0,
                       tracing=TraceConfig(enabled=True, pressure_every=30.0))
    bus = res.trace
    assert bus is not None and bus.count("launch") > 0
    assert bus.count("pressure") > 0
    untraced = run_scenario("smoke_40x2", scheduler="adaptive", seed=0)
    assert untraced.trace is None and untraced.makespan == res.makespan
    with pytest.raises(ValueError, match="indexed engine"):
        run_scenario("smoke_40x2", engine="legacy", tracing=True)
    # canonical JSONL: every line is a sorted-key record with t/kind
    p = write_jsonl(bus, tmp_path / "t.jsonl")
    lines = p.read_text().splitlines()
    assert len(lines) == len(bus.events)
    rec = json.loads(lines[0])
    assert "t" in rec and "kind" in rec
    assert lines[0] == dumps_canonical(rec)
    # Chrome trace_event JSON: X slices for task executions, with the
    # machine as pid and the VM as tid; instants and counters alongside
    c = write_chrome_trace(bus, tmp_path / "t.chrome.json")
    doc = json.loads(c.read_text())
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert xs and all({"pid", "tid", "ts", "dur"} <= set(e) for e in xs)
    assert any(e["ph"] == "C" for e in doc["traceEvents"])
    summary = fold_trace(bus, res.makespan)
    assert summary.maps_local + summary.maps_remote == bus.count("launch") \
        - summary.reduces - summary.speculative
    assert summary.locality_rate() == pytest.approx(res.locality_rate())


# -- warehouse integration ----------------------------------------------------

def _cell(seed=0):
    from repro.experiments.runner import Cell, TraceRef
    return Cell(trace=TraceRef(preset="mix_small"),
                cluster=ClusterSpec(num_machines=8, vms_per_machine=2),
                scheduler=PolicySpec("adaptive"), seed=seed,
                straggler_prob=0.05, straggler_factor=3.0,
                speculative=True, speculation_threshold=2.0)


def test_simulate_cell_traced_reproduces_the_cached_record(tmp_path):
    from repro.experiments.runner import simulate_cell
    from repro.experiments.telemetry import (fold_trace, simulate_cell_traced,
                                             store_trace_summary)
    cell = _cell()
    plain = simulate_cell(cell)             # dict, as the cache stores it
    record, bus = simulate_cell_traced(cell)
    assert record.makespan == plain["makespan"]
    assert record.locality_rate == plain["locality_rate"]
    assert record.cluster == plain["cluster"]   # tracing not in the dict
    summary = fold_trace(bus, record.makespan)
    path = store_trace_summary(tmp_path, cell, summary)
    from repro.experiments.runner import _cell_paths
    cell_dir, result_path = _cell_paths(tmp_path, cell)
    assert path == cell_dir / f"seed{cell.seed}.trace.json"
    loaded = json.loads(path.read_text())
    assert loaded["counts"] == dict(bus.counts)
    assert loaded["locality_rate"] == pytest.approx(record.locality_rate)


def test_explain_cell_attributes_decisions(tmp_path):
    from repro.experiments.telemetry import explain_cell
    text, pol, base = explain_cell(
        "saturated", "20x2", cache_dir=tmp_path,
        export_dir=tmp_path / "export")
    assert "attribution:" in text
    assert "latch" in text
    assert pol.park_admits + sum(pol.park_denies.values()) > 0
    assert (tmp_path / "export").exists()
    assert any((tmp_path / "export").glob("*.chrome.json"))


# -- CLI ----------------------------------------------------------------------

def test_cli_faults_list(capsys):
    from repro.experiments.__main__ import main
    assert main(["faults", "--list"]) == 0
    out = capsys.readouterr().out
    from repro.experiments.regimes import FAULT_PROFILES
    for name in FAULT_PROFILES:
        assert name in out


def test_cli_explain(tmp_path, capsys):
    from repro.experiments.__main__ import main
    assert main(["explain", "saturated", "20x2", "--cache", str(tmp_path),
                 "--no-store"]) == 0
    out = capsys.readouterr().out
    assert "attribution:" in out and "denied by gate" in out
    with pytest.raises(SystemExit):
        main(["explain", "nope", "20x2"])
    with pytest.raises(SystemExit):
        main(["explain", "saturated", "13x7"])


# -- wall-clock spans ---------------------------------------------------------

def test_span_carries_the_request_it_is_opened_in(monkeypatch):
    """Spans inside ``request()`` carry its number; requests count up and
    nest; a span outside any request carries only its own arguments."""
    import contextlib
    import jax
    from repro.core import tracing
    seen = []

    def annotation(name, **args):
        seen.append((name, args))
        return contextlib.nullcontext()

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)
    with tracing.span("repro.x", lanes=2):
        pass
    with tracing.request() as a:
        with tracing.span("repro.y"):
            with tracing.request() as b:
                tracing.span("repro.z", jobs=8)
        tracing.span("repro.w")
    assert b == a + 1
    assert seen == [("repro.x", {"lanes": 2}), ("repro.y", {"request": a}),
                    ("repro.z", {"jobs": 8, "request": b}),
                    ("repro.w", {"request": a})]


def test_pack_span_counts_the_lanes_packing_reorders(monkeypatch):
    """``repro.surrogate.pack`` carries ``reordered``, the lanes whose
    priority order moves jobs: none for FIFO on a submit-ordered trace,
    otherwise each EDF lane whose deadlines are out of job order."""
    import contextlib
    import jax
    import numpy as np
    from repro.simcluster.surrogate import build_cell, run_batch
    from repro.simcluster.traces import PRESETS, generate_trace
    seen = []

    def annotation(name, **args):
        seen.append((name, args))
        return contextlib.nullcontext()

    def reordered(cells):
        seen.clear()
        run_batch(cells)
        return [args["reordered"] for name, args in seen
                if name == "repro.surrogate.pack"]

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)
    trace = generate_trace(PRESETS["mix_small"], seed=0)
    cluster = ClusterSpec(num_machines=6, vms_per_machine=2, replication=1)
    fifo = [build_cell(trace, cluster, "fifo", seed) for seed in (0, 1)]
    assert all((np.diff(c.submit) >= 0).all() for c in fifo)
    assert reordered(fifo) == [0]
    edf = build_cell(trace, cluster, "edf_nopark", 0)
    in_order = dataclasses.replace(edf, dl_abs=np.sort(edf.dl_abs))
    lanes = [edf, in_order, edf]
    out_of_order = sum(bool((np.diff(c.dl_abs) < 0).any()) for c in lanes)
    assert out_of_order == 2
    assert reordered(lanes) == [out_of_order]


def test_job_specs_span_counts_the_blocks_left_unplaced(monkeypatch):
    """``repro.surrogate.job_specs`` opens once a cell built and carries
    ``blocks``, the cell's map blocks (its summed ``u_m``), none of which
    is placed."""
    import contextlib
    import jax
    from repro.simcluster.surrogate import build_cell
    from repro.simcluster.traces import PRESETS, generate_trace
    seen = []

    def annotation(name, **args):
        seen.append((name, args))
        return contextlib.nullcontext()

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)
    cluster = ClusterSpec(num_machines=20, vms_per_machine=2, replication=1)
    for preset in ("mix_small", "heavy_tail"):
        seen.clear()
        cell = build_cell(generate_trace(PRESETS[preset], seed=1), cluster,
                          "proposed", 0)
        (args,) = [a for name, a in seen
                   if name == "repro.surrogate.job_specs"]
        assert args == {"blocks": int(cell.u_m.sum())}
        assert args["blocks"] > cell.n_jobs


def test_cli_surrogate_profile_records_the_program_spans(tmp_path, capsys):
    """``surrogate --profile DIR`` leaves a profiler trace whose host plane
    holds the sweep's ``repro.surrogate.*`` spans."""
    import glob
    import jax
    from repro.experiments.__main__ import main
    assert main(["surrogate", "heavy_tail", "--seeds", "0",
                 "--policies", "proposed", "--no-calibrate",
                 "--cache", str(tmp_path / "cache"),
                 "--profile", str(tmp_path / "prof")]) == 0
    assert "[heavy_tail/20x2]" in capsys.readouterr().out
    run = tmp_path / "prof" / "plugins" / "profile" / "*"
    assert glob.glob(str(run / "perfetto_trace.json.gz"))
    (path,) = glob.glob(str(run / "*.xplane.pb"))
    names = {ev.name for plane in jax.profiler.ProfileData.from_file(
        path).planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events}
    assert {"repro.surrogate.sweep", "repro.surrogate.job_specs",
            "repro.surrogate.unpack"} <= names
