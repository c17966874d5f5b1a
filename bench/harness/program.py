"""The system under test, as the benchmark drives it.

Every request goes through the users' entry point,
``repro.experiments.surrogate.run_surrogate(spec, cache_dir)``: the
program resolves the trace recipe into jobs, builds the cell inputs,
integrates them on the device and writes one ``RunRecord`` per cell.  This
module only turns a configuration file and a request into an
``ExperimentSpec``, and wraps the layer entry points that
``run_surrogate`` calls so that the benchmark can see them: the bucket
shapes ``run_batch`` is given, and in a traced run a
``jax.profiler.TraceAnnotation`` around the host build and around
``run_batch``.
"""
from __future__ import annotations

import contextlib
import time
from typing import List, Set, Tuple

#: host-span names, as the trace reduction and the metric readers read them
SPAN_WINDOW = "bench.window"
SPAN_REQUEST = "bench.request"
SPAN_BUILD = "bench.build"
SPAN_ENGINE = "bench.run_batch"


def trace_recipe(config: dict) -> dict:
    """The configuration's trace recipe with its job count."""
    recipe = dict(config["trace"])
    recipe["num_jobs"] = int(config["num_jobs"])
    return recipe


#: configuration ``cluster`` keys the program's ``AdaptiveConfig`` takes
ADAPTIVE_KEYS = ("overload_pending_factor", "overload_active_factor")


def cluster_spec(config: dict, keys=None):
    """The deployment as the program's ``ClusterSpec``.  ``keys`` are the
    ``cluster`` keys the cell's reference models (``CLUSTER_KEYS``; the
    default reference's where ``None``).  A key outside them, or one the
    program does not take, is an error, so the program and the reference
    never run different deployments from one file.  The adaptive keys go
    to ``AdaptiveConfig``; every other key, nested groups such as
    ``faults`` and ``serve`` among them, to ``ClusterSpec.from_dict``."""
    import dataclasses
    from repro.core.types import AdaptiveConfig, ClusterSpec
    if keys is None:
        from harness.reference import CLUSTER_KEYS as keys
    c = dict(config["cluster"])
    unknown = sorted(set(c) - set(keys))
    if unknown:
        raise ValueError(f"cluster keys the reference does not model: "
                         f"{unknown}")
    adaptive = AdaptiveConfig(**{k: c.pop(k) for k in ADAPTIVE_KEYS if k in c})
    taken = {f.name for f in dataclasses.fields(ClusterSpec)} - {"adaptive"}
    unknown = sorted(set(c) - taken)
    if unknown:
        raise ValueError(f"cluster keys the program does not take: {unknown}")
    return ClusterSpec.from_dict(dict(c, adaptive=adaptive))


def spec(cell, request):
    """The request to the cell ``cell`` (a ``registry.CellSpec``) as the
    program's ``ExperimentSpec``.  A recipe trace is one ``TraceConfig`` run
    at every seed of the request; a job-type trace is one row set per seed
    (``harness.jobtypes``), with the deadlines of the cell's reference."""
    from repro.experiments.runner import ExperimentSpec, TraceRef
    from repro.simcluster.traces import TraceConfig
    from harness import jobtypes
    config = cell.config
    recipe = trace_recipe(config)
    if jobtypes.is_job_types(recipe):
        traces = tuple(
            TraceRef(rows=tuple(jobtypes.rows(recipe, s,
                                              cell.reference.deadline)),
                     name=jobtypes.trace_name(recipe, s), seed=s)
            for s in request.seeds)
        seeds = (jobtypes.SIM_SEED,)
    else:
        traces = (TraceRef(config=TraceConfig.from_dict(recipe)),)
        seeds = tuple(request.seeds)
    return ExperimentSpec(
        name=f"{cell.name}-{request.index}", traces=traces,
        clusters=(cluster_spec(config, cell.reference.CLUSTER_KEYS),),
        schedulers=tuple(request.policies), seeds=seeds)


def request_cells(config: dict, request):
    """The request's (policy, trace seed) cells in the order the program
    batches them (``ExperimentSpec.cells``: trace-major)."""
    from harness import jobtypes
    if jobtypes.is_job_types(config["trace"]):
        return [(p, s) for s in request.seeds for p in request.policies]
    return [(p, s) for p in request.policies for s in request.seeds]


def serve(experiment, cache_dir):
    """One request through the users' entry point."""
    from repro.experiments.surrogate import run_surrogate
    return run_surrogate(experiment, cache_dir)


class Probes:
    """Wraps ``build_inputs`` and ``run_batch`` where ``run_surrogate``
    looks them up, for the life of the context.  Each call's host-clock
    seconds are kept (``build_s``, ``batch_s``: one entry a request), so a
    slow request can be put down to its layer in any run."""

    def __init__(self):
        self.buckets: Set[Tuple[int, int, int]] = set()
        self.annotate = False
        self.build_s: List[float] = []
        self.batch_s: List[float] = []

    @contextlib.contextmanager
    def installed(self):
        import jax
        from repro.experiments import surrogate as front
        build0, batch0 = front.build_inputs, front.run_batch

        def timed(fn, span, kept, *args, **kw):
            start = time.perf_counter()
            try:
                if not self.annotate:
                    return fn(*args, **kw)
                with jax.profiler.TraceAnnotation(span):
                    return fn(*args, **kw)
            finally:
                kept.append(time.perf_counter() - start)

        def build_inputs(cells):
            return timed(build0, SPAN_BUILD, self.build_s, cells)

        def run_batch(inputs, **kw):
            groups = {}
            for cell in inputs:
                key = (cell.padded_jobs(), cell.n_steps())
                groups[key] = groups.get(key, 0) + 1
            self.buckets.update((n, j, s) for (j, s), n in groups.items())
            return timed(batch0, SPAN_ENGINE, self.batch_s, inputs, **kw)

        front.build_inputs, front.run_batch = build_inputs, run_batch
        try:
            yield self
        finally:
            front.build_inputs, front.run_batch = build0, batch0
