"""Finds a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own under the benchmark's directory, so a new cell or metric is
new files and entries, never an edit:

- ``<file>`` named by the configuration's entry: the deployment's sizes;
- ``bench/traffic/<traffic>.json``: the traffic mix's parameters;
- ``bench/metrics/<metric>.py``: a reader with ``read(ctx)`` that returns
  the metric's value, or ``None`` where it finds nothing to read;
- the Python file under ``bench/`` that the configuration names as its
  ``"reference"`` (a path from the checkout root): the plain reference that
  models the deployment and decides the cell's ``correct``.  Without the
  key it is ``harness.reference``.  A reference declares the ``cluster``
  keys it models (``CLUSTER_KEYS``), so a deployment with a mechanism of its
  own brings its reference and its keys as new files.  It may also add
  numbers to the cell's check (``EXTRA_NUMBERS`` with ``record_numbers``,
  ``harness.check``), each with its limit in ``bench/checks/<cell>.json``.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

from harness import reference as default_reference

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
    reference: ModuleType


#: what every reference module exposes
REFERENCE_API = ("CLUSTER_KEYS", "lower", "build", "answer", "deadline")
#: what a reference may expose besides: ``EXTRA_NUMBERS``, a tuple of names
#: that its ``answer`` also returns, and ``record_numbers(rec)``, which reads
#: the same names off a program ``RunRecord``; each is compared as
#: ``<name>_max_abs``.  Without them the check compares ``check.NUMBERS``.
OPTIONAL_API = ("EXTRA_NUMBERS", "record_numbers")


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _load(path: Path, name: str) -> ModuleType:
    """The Python file ``path`` as a module named ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _module_name(prefix: str, name: str) -> str:
    return prefix + "".join(c if c.isalnum() else "_" for c in name)


def _reader(root: Path, name: str) -> Callable:
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"metric {name!r} has no reader at {path}")
    return _load(path, _module_name("bench_metric_", name)).read


def _reference(root: Path, config: dict) -> ModuleType:
    """The reference the configuration names, or the default one."""
    rel = config.get("reference")
    if rel is None:
        return default_reference
    bench = (root / "bench").resolve()
    path = (root / rel).resolve()
    if (Path(rel).is_absolute() or path.suffix != ".py"
            or not path.is_relative_to(bench)):
        raise ValueError(f"reference {rel!r} is not a Python file under "
                         f"bench/ of the checkout")
    if not path.is_file():
        raise FileNotFoundError(f"reference {rel!r} has no file at {path}")
    module = _load(path, _module_name("bench_reference_", rel))
    missing = [a for a in REFERENCE_API if not hasattr(module, a)]
    if getattr(module, "EXTRA_NUMBERS", ()):
        missing += [a for a in OPTIONAL_API if not hasattr(module, a)]
    if missing:
        raise AttributeError(f"reference {rel!r} lacks {missing}")
    return module


def _applies(entry: dict, workload: str,
             e2e_names: Optional[set] = None) -> bool:
    if "workloads" in entry:
        return workload in entry["workloads"]
    if e2e_names is None:
        return True
    return entry["moves"] in e2e_names


def find_cell(workload: str, root: Path = ROOT) -> CellSpec:
    """The cell named ``workload`` with its configuration, its reference,
    its traffic mix and the readers of the metrics it reports."""
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
                    end_to_end=e2e, per_layer=layer,
                    reference=_reference(root, config))


def read_metrics(metrics: List[Metric], ctx) -> Dict[str, dict]:
    """``{name: {"value", "unit"}}`` of every metric whose reader found
    something to read."""
    out = {}
    for m in metrics:
        value = m.read(ctx)
        if value is not None:
            out[m.name] = {"value": float(value), "unit": m.unit}
    return out
