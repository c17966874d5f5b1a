"""Finds a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own under the benchmark's directory, so a new cell or metric is
new files and entries, never an edit:

- ``<file>`` named by the configuration's entry: the deployment's sizes;
- ``bench/traffic/<traffic>.json``: the traffic mix's parameters;
- ``bench/metrics/<metric>.py``: a reader with ``read(ctx)`` that returns
  the metric's value, or ``None`` where it finds nothing to read.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

#: the checkout root: ``bench/harness/`` is two levels below it
ROOT = Path(__file__).resolve().parents[2]


@dataclass
class Metric:
    name: str
    unit: str
    read: Callable


@dataclass
class CellSpec:
    """One cell of the benchmark with everything its files hold."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _reader(root: Path, name: str) -> Callable:
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _applies(entry: dict, workload: str,
             e2e_names: Optional[set] = None) -> bool:
    if "workloads" in entry:
        return workload in entry["workloads"]
    if e2e_names is None:
        return True
    return entry["moves"] in e2e_names


def find_cell(workload: str, root: Path = ROOT) -> CellSpec:
    """The cell named ``workload`` with its configuration, traffic mix and
    the readers of the metrics it reports."""
    root = Path(root)
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"available: {', '.join(sorted(cells))}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    e2e = [Metric(m["name"], m["unit"], _reader(root, m["name"]))
           for m in bench["end_to_end"] if _applies(m, workload)]
    names = {m.name for m in e2e}
    layer = [Metric(m["name"], m["unit"], _reader(root, m["name"]))
             for m in bench["per_layer"] if _applies(m, workload, names)]
    return CellSpec(name=workload, chips=int(cell["chips"]),
                    config_name=cell["config"], config=config,
                    traffic_name=cell["traffic"], traffic=traffic,
                    end_to_end=e2e, per_layer=layer)


def read_metrics(metrics: List[Metric], ctx) -> Dict[str, dict]:
    """``{name: {"value", "unit"}}`` of every metric whose reader found
    something to read."""
    out = {}
    for m in metrics:
        value = m.read(ctx)
        if value is not None:
            out[m.name] = {"value": float(value), "unit": m.unit}
    return out
