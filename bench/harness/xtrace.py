"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Its device planes (``/device:TPU:0`` ...) carry an ``XLA Ops`` line, one
event per operation run on the device, and an ``XLA Modules`` line, one
event per executable run.  The host plane (``/host:CPU``) carries the
benchmark's own spans (``jax.profiler.TraceAnnotation``), on the same clock.
All times here are in seconds on that clock.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: idle gaps shorter than this are the seams between back-to-back ops
MIN_GAP_S = 1e-6


def op_name(text: str) -> str:
    """An operation's short name: its HLO text up to `` = ``."""
    return text.split(" = ", 1)[0]


def self_seconds(events: Sequence[Tuple[str, float, float]]
                 ) -> Dict[str, float]:
    """Exclusive device seconds per operation name: an op that encloses
    others on its line (a ``while`` around its body) keeps only the time
    no enclosed op covers."""
    out: Dict[str, float] = {}
    stack: List[list] = []      # [name, end, self seconds]

    def close(item):
        out[item[0]] = out.get(item[0], 0.0) + max(item[2], 0.0)

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            close(stack.pop())
        if stack:
            stack[-1][2] -= b - a
        stack.append([name, b, b - a])
    while stack:
        close(stack.pop())
    return out


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """The union of ``intervals`` as sorted, disjoint intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def covered(merged: Sequence[Interval], a: float, b: float) -> float:
    """Seconds of ``[a, b]`` that the disjoint sorted ``merged`` covers."""
    if b <= a or not merged:
        return 0.0
    i = max(bisect.bisect_right([s for s, _ in merged], a) - 1, 0)
    total = 0.0
    while i < len(merged) and merged[i][0] < b:
        s, e = merged[i]
        total += max(0.0, min(e, b) - max(s, a))
        i += 1
    return total


@dataclass
class Reduced:
    """What the trace says, per device plane merged over the chips used."""

    #: device-op intervals of each device plane, merged
    busy: Dict[str, List[Interval]] = field(default_factory=dict)
    #: exclusive device seconds per operation name, summed over planes
    op_seconds: Dict[str, float] = field(default_factory=dict)
    #: executable runs on the device: (module name, start, end)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)
    #: host spans: name -> [(start, end)]
    spans: Dict[str, List[Interval]] = field(default_factory=dict)

    def window(self, name: str) -> Optional[Interval]:
        """First to last instant of the host spans called ``name``."""
        spans = self.spans.get(name)
        if not spans:
            return None
        return min(a for a, _ in spans), max(b for _, b in spans)

    def busy_s(self, a: float, b: float) -> float:
        """Device-busy seconds in ``[a, b]``, averaged over the planes."""
        if not self.busy:
            return 0.0
        return sum(covered(m, a, b) for m in self.busy.values()) \
            / len(self.busy)

    def busy_in(self, spans: Sequence[Interval]) -> float:
        """Device-busy seconds inside the host spans ``spans``."""
        return sum(self.busy_s(a, b) for a, b in merge(spans))

    def span_seconds(self, name: str) -> float:
        return sum(b - a for a, b in self.spans.get(name, ()))

    def module_seconds(self, prefix: str, within: Optional[Interval] = None
                       ) -> float:
        """Device seconds of executables whose name starts with
        ``prefix``, averaged over the planes."""
        total = 0.0
        for name, a, b in self.modules:
            if name.startswith(prefix):
                if within is not None:
                    a, b = max(a, within[0]), min(b, within[1])
                total += max(0.0, b - a)
        return total / max(len(self.busy), 1)

    def top_ops(self, n: int = 10) -> List[List]:
        ranked = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in ranked[:n]]

    def idle_gaps(self, window: Interval, n: int = 10) -> List[List]:
        """The longest idle gaps of the first device plane inside
        ``window``, each named by the innermost host span around its
        middle (``"no span"`` where none)."""
        if not self.busy:
            return []
        merged = next(iter(self.busy.values()))
        a, b = window
        gaps, t = [], a
        for s, e in merged:
            if e <= a:
                continue
            if s >= b:
                break
            if s - t > MIN_GAP_S:
                gaps.append((t, s))
            t = max(t, e)
        if b - t > MIN_GAP_S:
            gaps.append((t, b))
        named = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            mid = 0.5 * (s + e)
            inner = None
            for name, spans in self.spans.items():
                for x, y in spans:
                    if x <= mid <= y and (inner is None
                                          or y - x < inner[1]):
                        inner = (name, y - x)
            named.append([inner[0] if inner else "no span", e - s])
        return named


def latest_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def reduce_file(path: str, span_prefix: str = "bench.") -> Reduced:
    """Read one ``.xplane.pb`` (or ``.xplane.pb.gz``) into a
    :class:`Reduced`."""
    import gzip
    import jax
    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        data = jax.profiler.ProfileData.from_file(str(path))
    out = Reduced()
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops: List[Interval] = []
            named = []
            modules = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        a = ev.start_ns * 1e-9
                        b = a + ev.duration_ns * 1e-9
                        ops.append((a, b))
                        named.append((op_name(ev.name), a, b))
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        a = ev.start_ns * 1e-9
                        modules.append((ev.name, a,
                                        a + ev.duration_ns * 1e-9))
            for name, sec in self_seconds(named).items():
                out.op_seconds[name] = out.op_seconds.get(name, 0.0) + sec
            if not ops:
                ops = [(a, b) for _, a, b in modules]
            if ops:
                out.busy[plane.name] = merge(ops)
                out.modules.extend(modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        a = ev.start_ns * 1e-9
                        out.spans.setdefault(ev.name, []).append(
                            (a, a + ev.duration_ns * 1e-9))
    return out
