#!/usr/bin/env python3
"""Chip smoke test: the fleet-scale surrogate sweep on one TPU chip.

Drives the batched fluid surrogate through its normal entry points
(``run_surrogate``, ``run_batch``, ``calibrate``) in one process and checks
what comes out:

1. device: JAX's default backend must be a TPU, else exit non-zero;
2. fleet sweep: the heavy_tail preset on a 200x2 fleet at replication 2,
   its backlog scaled to the fleet (800 jobs per cell, padded to the
   1024-job x 4096-step bucket), the five lowerable policies x seeds 0-15 =
   80 cells, integrated cold into a fresh cache, then re-integrated warm
   with every shape compiled beforehand (no compile in the warm window);
3. same-implementation reference: one seed's five cells on the CPU
   backend of this process, compared with the chip's results;
4. determinism pins on the chip, bit for bit: a batch of one equals the
   unbatched kernel, and reversed order or another sub-batch cap changes
   no result;
5. plain reference: the heavy_tail/20x2 calibration wall against the
   event oracle, with the surrogate side on the chip.

The last line of standard output, printed only when every phase passed, is
one JSON object naming the device.  Usage, on a machine with one chip::

    python chip_smoke.py
"""
from __future__ import annotations

import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

POLICIES = ("proposed", "fair", "fifo", "delay", "edf_nopark")
SEEDS = tuple(range(16))
MACHINES = 200
#: chip vs CPU on the same inputs: float32 ``exp``/``log1p`` and reduction
#: order may differ by ULPs, and the integer lag rounding can turn one into
#: a whole 6 s step, so makespan may move by a few steps and locality by a
#: little launch mass.  Counts of finished jobs and met deadlines must agree.
MAKESPAN_RTOL = 0.01
LOCALITY_ATOL = 0.01


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def fingerprint(res):
    """Every float the RunRecord surface consumes, exact."""
    return (res.makespan, res.jobs_total, res.jobs_finished,
            res.deadlines_met, res.locality_rate, res.latched_steps,
            tuple((j.job_id, j.finish_time, j.completion_time,
                   j.deadline_met, j.local_map_launches,
                   j.remote_map_launches) for j in res.jobs))


class CompileCounter:
    """Counts executables JAX lowers: each new one is lowered before it is
    compiled or loaded from the persistent cache."""

    def __init__(self):
        import jax
        self.lowered = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def fleet_spec():
    from repro.core.types import ClusterSpec
    from repro.experiments.regimes import scaled_jobs
    from repro.experiments.runner import ExperimentSpec, TraceRef
    from repro.simcluster.traces import PRESETS
    cfg = dataclasses.replace(PRESETS["heavy_tail"],
                              num_jobs=scaled_jobs("heavy_tail", MACHINES))
    return ExperimentSpec(
        name="chip-smoke-fleet", traces=(TraceRef(config=cfg),),
        clusters=(ClusterSpec(num_machines=MACHINES, vms_per_machine=2,
                              replication=2),),
        schedulers=POLICIES, seeds=SEEDS)


def phase_sweep(spec, counter, label):
    """Cold sweep through run_surrogate, then a warm run_batch window."""
    from repro.experiments.surrogate import build_inputs, run_surrogate
    from repro.simcluster.surrogate import run_batch
    n_cells = spec.n_cells()
    with tempfile.TemporaryDirectory() as cache:
        lowered0, hits0 = counter.lowered, counter.cache_hits
        t0 = time.perf_counter()
        report = run_surrogate(spec, cache)
        cold_s = time.perf_counter() - t0
    print(f"[sweep] {label}: cold run_surrogate {n_cells} cells in "
          f"{cold_s:.3f} s (build + compile + integrate; "
          f"{counter.lowered - lowered0} compiles, "
          f"{counter.cache_hits - hits0} persistent-cache hits)")
    require(report.simulated == n_cells and report.cached == 0,
            f"expected {n_cells} simulated / 0 cached, got "
            f"{report.simulated} / {report.cached}")
    jobs = {r.jobs_total for r in report.records}
    require(jobs == {spec.traces[0].config.num_jobs},
            f"cells hold {jobs} jobs, expected "
            f"{spec.traces[0].config.num_jobs}")
    unfinished = [(r.scheduler, r.seed, r.jobs_finished)
                  for r in report.records
                  if r.jobs_finished != r.jobs_total]
    require(not unfinished, f"cells with unfinished jobs: {unfinished}")
    print(f"[sweep] {report.simulated} cells integrated, {report.cached} "
          f"cached, every cell finished all {sorted(jobs)} jobs")

    cells = list(spec.cells())
    t0 = time.perf_counter()
    _, inputs = build_inputs(cells)
    build_s = time.perf_counter() - t0
    buckets = sorted({(c.padded_jobs(), c.n_steps()) for c in inputs})
    print(f"[sweep] host build of {len(inputs)} cells: {build_s:.3f} s; "
          f"(jobs, steps) buckets {buckets}")
    # the same call as the timed one below, so every (bucket, sub-batch
    # size) shape the window uses is compiled before it opens
    warmup = run_batch(inputs)
    lowered0 = counter.lowered
    t0 = time.perf_counter()
    warm = run_batch(inputs)
    warm_s = time.perf_counter() - t0
    in_window = counter.lowered - lowered0
    print(f"[sweep] {label}: warm run_batch {len(inputs)} cells in "
          f"{warm_s:.3f} s = {len(inputs) / warm_s:.3f} cells/s "
          f"(pack + device + unpack); compiles in window: {in_window}")
    require(in_window == 0, f"{in_window} compiles inside the warm window")
    by_key = {(r.scheduler, r.seed): r for r in report.records}
    for cell, a, b in zip(cells, warmup, warm):
        rec = by_key[(cell.scheduler.label, cell.seed)]
        require(fingerprint(a) == fingerprint(b)
                and (rec.makespan, rec.deadlines_met, rec.locality_rate)
                == (b.makespan, b.deadlines_met, b.locality_rate),
                f"{cell.scheduler.label}/seed{cell.seed}: repeat runs "
                f"on the chip disagree")
    print("[sweep] warm-up and warm results identical bit for bit, and "
          "equal to the cold records")
    return cells, inputs, warm


def phase_cpu_reference(cells, inputs, chip):
    """Seed 0's five cells on this process's CPU backend vs the chip."""
    import jax
    from repro.simcluster.surrogate import run_batch
    rows = [i for i, c in enumerate(cells) if c.seed == SEEDS[0]]
    with jax.default_device(jax.devices("cpu")[0]):
        ref = run_batch([inputs[i] for i in rows])
    worst_ms = worst_loc = worst_finish = 0.0
    moved = 0
    for i, r in zip(rows, ref):
        c = chip[i]
        name = f"{cells[i].scheduler.label}/seed{cells[i].seed}"
        require(c.jobs_finished == r.jobs_finished,
                f"{name}: jobs_finished chip {c.jobs_finished} "
                f"!= cpu {r.jobs_finished}")
        require(c.deadlines_met == r.deadlines_met,
                f"{name}: deadlines_met chip {c.deadlines_met} "
                f"!= cpu {r.deadlines_met}")
        d_ms = abs(c.makespan - r.makespan) / r.makespan
        d_loc = abs(c.locality_rate - r.locality_rate)
        require(d_ms <= MAKESPAN_RTOL,
                f"{name}: makespan chip {c.makespan} vs cpu {r.makespan} "
                f"(rel {d_ms:.3g} > {MAKESPAN_RTOL})")
        require(d_loc <= LOCALITY_ATOL,
                f"{name}: locality chip {c.locality_rate} vs cpu "
                f"{r.locality_rate} ({d_loc:.3g} > {LOCALITY_ATOL})")
        for jc, jr in zip(c.jobs, r.jobs):
            if jc.finish_time != jr.finish_time:
                moved += 1
                worst_finish = max(worst_finish,
                                   abs(jc.finish_time - jr.finish_time))
        worst_ms, worst_loc = max(worst_ms, d_ms), max(worst_loc, d_loc)
        print(f"[cpu-ref] {name}: makespan chip {c.makespan} cpu "
              f"{r.makespan}; locality chip {c.locality_rate} cpu "
              f"{r.locality_rate}; deadlines {c.deadlines_met}")
    print(f"[cpu-ref] {len(rows)} cells: jobs_finished and deadlines_met "
          f"equal; largest makespan diff {worst_ms:.6g} (rel, tol "
          f"{MAKESPAN_RTOL}), largest locality diff {worst_loc:.6g} (abs, "
          f"tol {LOCALITY_ATOL}); {moved} job finish times differ, by at "
          f"most {worst_finish} s")


def phase_determinism(cells, inputs, chip):
    """The sweep cache's contract, on the chip, bit for bit."""
    from repro.simcluster.surrogate import run_batch, run_cell
    base = [fingerprint(r) for r in chip]
    rows = [i for i, c in enumerate(cells) if c.seed == SEEDS[0]]
    for i in rows:
        single = run_batch([inputs[i]])[0]
        require(fingerprint(single) == fingerprint(run_cell(inputs[i])),
                f"{cells[i].scheduler.label}/seed{cells[i].seed}: batch of "
                f"one != run_cell")
    print(f"[pins] batch of one == run_cell on {len(rows)} cells")
    flipped = [fingerprint(r) for r in run_batch(inputs[::-1])][::-1]
    require(flipped == base, "reversed batch order moved a result")
    print(f"[pins] reversed order == forward order on {len(inputs)} cells")
    for cap in (64, 16, 1):
        t0 = time.perf_counter()
        got = [fingerprint(r) for r in run_batch(inputs, max_batch=cap)]
        took = time.perf_counter() - t0
        require(got == base, f"max_batch={cap} moved a result")
        print(f"[pins] max_batch={cap} == default split on "
              f"{len(inputs)} cells ({took:.3f} s, including any compile)")


def phase_calibration():
    """The event oracle's paired CI vs the surrogate's gain, on the chip."""
    from repro.experiments.surrogate import calibrate
    with tempfile.TemporaryDirectory() as cache:
        t0 = time.perf_counter()
        report = calibrate("heavy_tail", "20x2", cache, workers=0)
        wall_s = time.perf_counter() - t0
    for p in report.policies:
        print(f"[calibrate] heavy_tail/20x2 {p.policy}: surrogate "
              f"{p.surrogate_gain_pct:+.4f}% vs oracle CI "
              f"[{p.oracle.ci_lo_pct:+.4f}%, {p.oracle.ci_hi_pct:+.4f}%] "
              f"{'IN' if p.inside else 'OUT'}"
              f"{'' if p.allowlisted else ' (not allowlisted)'}")
    require(report.wall_green, "heavy_tail/20x2 calibration wall is red")
    print(f"[calibrate] wall green ({wall_s:.3f} s, event oracle on the "
          f"host, surrogate on the chip)")


def main() -> int:
    import jax
    from repro.simcluster.surrogate import use_compile_cache

    devices = jax.devices()
    dev = devices[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} backend={jax.default_backend()}",
          flush=True)
    if jax.default_backend() != "tpu":
        print("FAIL: no TPU backend; this smoke test runs only on the chip",
              file=sys.stderr)
        return 1
    print(f"[device] compile cache: {use_compile_cache()}", flush=True)
    counter = CompileCounter()
    label = f"{dev.platform}/{dev.device_kind}"
    t0 = time.perf_counter()
    try:
        cells, inputs, chip = phase_sweep(fleet_spec(), counter, label)
        phase_cpu_reference(cells, inputs, chip)
        phase_determinism(cells, inputs, chip)
        phase_calibration()
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(f"[done] all phases passed in {time.perf_counter() - t0:.3f} s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
