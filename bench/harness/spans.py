"""The program's own spans in a profiler trace, for the readers that need
them.

The program opens ``repro.``-prefixed wall-clock spans around its layers
(``repro.core.tracing.span``), one ``jax.profiler.TraceAnnotation`` each,
on the ``/host:CPU`` plane and on the clock of the device's operations.
Each ``run_surrogate`` call is one ``repro.surrogate.sweep`` holding
``cache_lookup``, ``build`` (``resolve``, ``build_cell`` > ``job_specs``),
``run_batch`` (per sub-batch ``pack``, ``dispatch``, ``fetch``,
``unpack``) and ``records``; every span carries ``request=<n>``,
``dispatch`` its shape (``lanes``, ``jobs``, ``steps``) and ``unpack`` the
steps the sub-batch ran (``steps_run``, ``lane_steps_run``).

``harness.xtrace.reduce_file`` keeps the benchmark's ``bench.`` spans.
:func:`install` has it keep the program's too, with their arguments (a
:class:`Traced`), so the breakdown's idle gaps are named by the program's
innermost span.  A trace of a program that opens no such span reduces as
before, and every reader here finds nothing (``None``).
"""
from __future__ import annotations

from dataclasses import dataclass, field, fields
from typing import Dict, List, Optional

from harness import xtrace

PREFIX = "repro."
SWEEP = "repro.surrogate.sweep"
CACHE_LOOKUP = "repro.surrogate.cache_lookup"
BUILD = "repro.surrogate.build"
RESOLVE = "repro.surrogate.resolve"
BUILD_CELL = "repro.surrogate.build_cell"
JOB_SPECS = "repro.surrogate.job_specs"
RUN_BATCH = "repro.surrogate.run_batch"
PACK = "repro.surrogate.pack"
DISPATCH = "repro.surrogate.dispatch"
FETCH = "repro.surrogate.fetch"
UNPACK = "repro.surrogate.unpack"
RECORDS = "repro.surrogate.records"

#: the kernel stages that keep the in-flight rings
RING_STAGES = ("ring_drain", "ring_scatter")


@dataclass
class Traced(xtrace.Reduced):
    """A :class:`xtrace.Reduced` that also holds the program's spans (in
    ``spans``, beside the benchmark's) and their arguments."""

    #: span name -> one dict of arguments a span, in ``spans[name]``'s order
    args: Dict[str, List[dict]] = field(default_factory=dict)

    def self_seconds(self, name: str) -> float:
        """Seconds of the spans ``name`` that no span inside them covers."""
        inner = [iv for other, ivs in self.spans.items() for iv in ivs
                 if other != name]
        total = 0.0
        for a, b in self.spans.get(name, ()):
            kids = xtrace.merge((x, y) for x, y in inner
                                if a <= x and y <= b)
            total += (b - a) - xtrace.covered(kids, a, b)
        return total


#: the benchmark's own reduction, which this one extends
_base_reduce_file = xtrace.reduce_file


def reduce_file(path: str, span_prefix: str = "bench.") -> Traced:
    """``xtrace.reduce_file`` plus the program's spans and arguments."""
    import gzip
    import jax
    base = _base_reduce_file(path, span_prefix)
    out = Traced(**{f.name: getattr(base, f.name) for f in fields(base)})
    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        data = jax.profiler.ProfileData.from_file(str(path))
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    a = ev.start_ns * 1e-9
                    out.spans.setdefault(ev.name, []).append(
                        (a, a + ev.duration_ns * 1e-9))
                    out.args.setdefault(ev.name, []).append(
                        {k: v for k, v in ev.stats})
    return out


def install() -> None:
    """Make ``xtrace.reduce_file`` keep the program's spans (idempotent)."""
    if xtrace.reduce_file is not reduce_file:
        xtrace.reduce_file = reduce_file


def traced(ctx) -> Optional[Traced]:
    """The run's trace, where it holds the program's spans."""
    t = ctx.trace
    if ctx.traced_window is None or not isinstance(t, Traced) or not t.args:
        return None
    return t


def seconds(ctx, *names: str) -> Optional[float]:
    """Seconds of the spans ``names`` together, where each was opened."""
    t = traced(ctx)
    if t is None or not all(t.spans.get(n) for n in names):
        return None
    return sum(t.span_seconds(n) for n in names)


def arg_total(ctx, name: str, *keys: str) -> Optional[int]:
    """The sum over the spans ``name`` of the product of their ``keys``."""
    t = traced(ctx)
    if t is None or not t.args.get(name):
        return None
    total = 0
    for args in t.args[name]:
        if not all(k in args for k in keys):
            return None
        product = 1
        for k in keys:
            product *= int(args[k])
        total += product
    return total


def stage_map(lanes: int, jobs: int, steps: int) -> Optional[Dict[str, str]]:
    """``{operation name: kernel stage}`` of the executable that ran,
    from the program's ``kernel_stages``; ``None`` where it has none."""
    from repro.simcluster import surrogate
    kernel_stages = getattr(surrogate, "kernel_stages", None)
    if kernel_stages is None:
        return None
    return {name.lstrip("%"): stage for name, stage
            in kernel_stages(jobs, steps, lanes).items()}


def stage_seconds(ctx) -> Optional[Dict[str, float]]:
    """Exclusive device seconds of the kernel per stage, averaged over the
    planes, where the traced window ran one executable shape (operation
    names are unique within one executable only)."""
    t = traced(ctx)
    if t is None or not t.args.get(DISPATCH) or not t.op_seconds:
        return None
    shapes = {(a.get("lanes"), a.get("jobs"), a.get("steps"))
              for a in t.args[DISPATCH]}
    if len(shapes) != 1 or None in next(iter(shapes)):
        return None
    stages = stage_map(*(int(x) for x in next(iter(shapes))))
    if not stages:
        return None
    out: Dict[str, float] = {}
    planes = max(len(t.busy), 1)
    for name, sec in t.op_seconds.items():
        stage = stages.get(name.lstrip("%"))
        if stage is not None:
            out[stage] = out.get(stage, 0.0) + sec / planes
    return out
