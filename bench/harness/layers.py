"""Per-layer quantities the metric readers share, from the traced window.

The layers, from the entry point down (``PERF.md`` section 3):

- host build: ``experiments/surrogate.build_inputs`` (trace resolution,
  job specs, block placement, ``simcluster/surrogate.build_cell``), timed
  by the benchmark's span around it;
- engine host side: ``run_batch``'s packing, dispatch and
  ``_unpack_result``, plus ``run_surrogate``'s record writes: the request
  span less the build span and less the device-busy time inside the
  ``run_batch`` span;
- kernel: the surrogate executable (``_make_kernel``'s scan x vmap
  program) on the device;
- device: busy and idle over the traced window.
"""
from __future__ import annotations

from typing import Optional

from harness import program, workcount

#: the surrogate executable's name on the device (the jitted ``kernel``)
KERNEL_MODULE = "jit_kernel"


def _traced(ctx):
    if ctx.trace is None or ctx.traced_window is None:
        return None
    return ctx.trace


def per(ctx, unit: str) -> Optional[float]:
    """Cells (``unit="cell"``) or requests (``"query"``) in the traced
    window."""
    if unit == "cell":
        return float(ctx.cells()) or None
    return float(len(ctx.answered)) or None


def build_s(ctx) -> Optional[float]:
    t = _traced(ctx)
    if t is None or not t.spans.get(program.SPAN_BUILD):
        return None
    return t.span_seconds(program.SPAN_BUILD)


def engine_host_s(ctx) -> Optional[float]:
    t = _traced(ctx)
    if t is None or not t.spans.get(program.SPAN_REQUEST) \
            or not t.spans.get(program.SPAN_ENGINE):
        return None
    device = t.busy_in(t.spans[program.SPAN_ENGINE])
    return (t.span_seconds(program.SPAN_REQUEST)
            - t.span_seconds(program.SPAN_BUILD) - device)


def kernel_s(ctx) -> Optional[float]:
    t = _traced(ctx)
    if t is None:
        return None
    seconds = t.module_seconds(KERNEL_MODULE, ctx.traced_window)
    return seconds or None


def job_steps(ctx) -> int:
    total = 0
    for served in ctx.answered:
        for rec in served.records:
            total += workcount.job_steps(
                rec.jobs_total, [j.finish_time for j in rec.jobs])
    return total


def idle_share(ctx) -> Optional[float]:
    t = _traced(ctx)
    if t is None or not t.busy:
        return None
    a, b = ctx.traced_window
    return 1.0 - t.busy_s(a, b) / (b - a)
