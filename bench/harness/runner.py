"""One run of one cell: set-up, the measured window, the check, the result.

1. Set-up: read the cell's files, require the chip, turn on JAX's
   persistent compile cache at ``<checkout>/.jax_cache/``, and serve one
   warm-up request of the window's size (its own seeds and draws), which
   compiles or loads every shape the window uses.
2. Window: one client's closed loop through ``run_surrogate``, each
   request sent when the last returned, into a results cache made fresh
   for the run, so every cell integrates.  It closes at the end of the
   first request that ends at or after ``--seconds``.  With ``--trace 1``
   the profiler records the first ``trace_requests`` requests, and the
   window closes after them.
3. Check: the device's memory peak is read, then the cell's plain
   reference answers a sample of the window's cells again
   (``harness.check``).
4. Result: the metrics the cell reports, read by their own readers.
"""
from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass, field
from pathlib import Path
from typing import List, Optional

from harness import check, program, registry
from harness.device import (CompileCounter, NoAccelerator,
                            memory_peak_bytes, require_accelerator)
from harness.traffic import Request, Stream


@dataclass
class Served:
    """One request as the window saw it."""

    request: Request
    start: float
    end: float
    ok: bool
    records: list = field(default_factory=list)

    @property
    def latency(self) -> float:
        return self.end - self.start


@dataclass
class Context:
    """What the metric readers read."""

    setup_s: float
    served: List[Served]
    window_s: float
    device_kind: str
    trace: Optional[object] = None
    traced_window: Optional[tuple] = None

    @property
    def answered(self) -> List[Served]:
        return [s for s in self.served if s.ok]

    def cells(self) -> int:
        return sum(s.request.cells for s in self.answered)


def _serve(cell, stream_request, cache_dir, traced) -> Served:
    import jax
    exp = program.spec(cell, stream_request)
    start = time.perf_counter()
    try:
        if traced:
            with jax.profiler.TraceAnnotation(program.SPAN_REQUEST):
                report = program.serve(exp, cache_dir)
        else:
            report = program.serve(exp, cache_dir)
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return Served(stream_request, start, time.perf_counter(), False)
    end = time.perf_counter()
    ok = (report.simulated == stream_request.cells and report.cached == 0
          and len(report.records) == stream_request.cells)
    if not ok:
        print(f"[bench] request {stream_request.index}: "
              f"{report.simulated} simulated, {report.cached} cached, "
              f"{len(report.records)} records for {stream_request.cells} "
              f"cells", file=sys.stderr)
    return Served(stream_request, start, end, ok, list(report.records))


def _window(cell, stream, seconds, cache_dir, traced, limit
            ) -> List[Served]:
    served: List[Served] = []
    opened = time.perf_counter()
    k = 0
    while True:
        served.append(_serve(cell, stream.request(k), cache_dir, traced))
        k += 1
        if served[-1].end - opened >= seconds or (limit and k >= limit):
            return served


def _checked(cell, served, seed) -> dict:
    """Compare a sample of the window's cells with the cell's reference."""
    R = cell.reference
    conf = dict(cell.config)
    conf["trace"] = program.trace_recipe(cell.config)
    cells, makespans, answers, groups = [], [], [], []
    for s in served:
        by_key = {(R.lower(rec.policy), rec.trace_seed): rec
                  for rec in s.records}
        groups.append([])
        for policy, sd in program.request_cells(cell.config, s.request):
            rec = by_key.get((R.lower(policy), sd))
            groups[-1].append(len(cells))
            cells.append((policy, sd))
            answers.append(None if rec is None
                           else check.record_answer(rec, R))
            makespans.append(-1.0 if rec is None else rec.makespan)
    picked = check.sample(groups, makespans, cell.traffic, cell.name, seed)
    refs = [R.answer(conf, cells[i][0], cells[i][1]) for i in picked]
    return check.compare([answers[i] for i in picked], refs, check.extra(R))


def run(workload: str, seed: int, seconds: float, trace: bool,
        t0: float, root: Path = registry.ROOT) -> dict:
    """One run of the cell ``workload``; returns the result object."""
    cell = registry.find_cell(workload, root)
    limit = check.limits(root, workload,
                         check.names(check.extra(cell.reference)))
    device = require_accelerator(cell.chips)
    from repro.simcluster.surrogate import use_compile_cache
    use_compile_cache()
    counter = CompileCounter()
    stream = Stream(cell.traffic, workload, seed)
    probes = program.Probes()
    workdir = Path(tempfile.mkdtemp(prefix="bench-run-"))
    try:
        with probes.installed():
            warm = _serve(cell, stream.warmup(), workdir / "records", False)
            if not warm.ok:
                raise RuntimeError("the warm-up request failed")
            setup_s = time.perf_counter() - t0
            setup_compiles = counter.lowered
            lowered0, hits0 = counter.lowered, counter.cache_hits
            reduced = traced_window = None
            if trace:
                import jax
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(str(workdir / "trace"),
                                         profiler_options=opts)
                probes.annotate = True
                try:
                    with jax.profiler.TraceAnnotation(program.SPAN_WINDOW):
                        served = _window(
                            cell, stream, seconds, workdir / "records", True,
                            int(cell.traffic["trace_requests"]))
                finally:
                    probes.annotate = False
                    jax.profiler.stop_trace()
            else:
                served = _window(cell, stream, seconds, workdir / "records",
                                 False, 0)
        window_s = served[-1].end - served[0].start
        in_window = counter.lowered - lowered0
        peak = memory_peak_bytes(cell.chips)
        numbers = _checked(cell, [s for s in served if s.ok], seed)
        if trace:
            from harness import xtrace
            reduced = xtrace.reduce_file(
                xtrace.latest_xplane(str(workdir / "trace")))
            traced_window = reduced.window(program.SPAN_WINDOW)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    failed = sum(not s.ok for s in served)
    ctx = Context(setup_s=setup_s, served=served,
                  window_s=window_s, device_kind=str(device["kind"]),
                  trace=reduced, traced_window=traced_window)
    metrics = registry.read_metrics(
        cell.per_layer if trace else cell.end_to_end, ctx)
    device = dict(device, memory_peak_bytes=peak)
    result = {"correct": check.verdict(numbers, limit) and failed == 0,
              "attempted": len(served), "failed": failed,
              "metrics": metrics, "device": device}
    if trace and reduced is not None and traced_window is not None:
        a, b = traced_window
        device["busy_s"] = reduced.busy_s(a, b)
        device["window_s"] = b - a
        result["breakdown"] = {
            "device_ops": reduced.top_ops(10),
            "idle_gaps": reduced.idle_gaps(traced_window, 10)}
    result["compared"] = check.report(numbers, limit)
    print(f"[bench] cell={workload} seed={seed} device platform="
          f"{device['platform']} kind={device['kind']} count={device['count']}"
          f" compiles_in_window={in_window} setup_compiles={setup_compiles}"
          f" setup_cache_hits={hits0} attempted={len(served)} failed={failed}"
          f" cells={ctx.cells()} window_s={window_s:.6f}"
          f" setup_s={setup_s:.6f} buckets(cells,jobs,steps)="
          f"{sorted(probes.buckets)}", flush=True)
    # the window's requests on the host clock: whole request, host build
    # and run_batch seconds (the warm-up's first), to put a slow run down
    # to its layer
    print("[bench] request_s=" + ",".join(f"{s.latency:.4f}" for s in served)
          + " build_s=" + ",".join(f"{x:.4f}" for x in probes.build_s)
          + " run_batch_s=" + ",".join(f"{x:.4f}" for x in probes.batch_s),
          flush=True)
    return result


def main(t0: float) -> int:
    """The command line: one run, its result as the last line of standard
    output, the numbers compared as the last lines of standard error."""
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), t0)
    except NoAccelerator as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    for name, entry in result["compared"].items():
        print(f"check {name} = {entry['value']!r} (limit {entry['limit']!r})",
              file=sys.stderr)
    print(f"check correct = {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
