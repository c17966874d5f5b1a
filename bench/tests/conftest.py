"""Shared set-up of the benchmark's CPU tests: the harness on the import
path, a checkout-shaped fixture root that holds tiny cells of its own, and
a copy of the real benchmark extended by a deployment added as new files
and appended entries only."""
import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CELL = "probe.tiny_20x2"
#: the grid's own traffic file and check rule on a tiny job-type deployment
GRID_CELL = "grid.tinytypes_20x2"
#: a deployment with a cluster key outside the default reference's, which
#: brings a reference of its own that models it
KEYED_CELL = "probe.keyed_20x2"
KEYED_REFERENCE = "bench/references/keyed.py"
#: a grid cell at fb2009_600x2's fleet whose reference models a cluster key
#: of its own and adds a number to the check, as a deployment with a
#: mechanism of its own does
PROBE_CELL = "grid.probe_600x2"
PROBE_CONFIG = "probe_600x2"
PROBE_REFERENCE = "bench/references/probe_makespan.py"
PROBE_METRIC = "probe_requests.grid"
PROBE_EXTRA = ("makespan",)
PROBE_REFERENCE_TEXT = '''\
"""The default model under a quiet fault layer, which also checks each
cell's makespan (its last finish time)."""
import numpy as np

from harness import reference as base
from harness.reference import build, deadline, lower

CLUSTER_KEYS = base.CLUSTER_KEYS + ("faults",)
EXTRA_NUMBERS = ("makespan",)


def record_numbers(rec):
    return {"makespan": float(rec.makespan)}


def answer(config, policy, seed, dtype=np.float32, cell=None):
    out = base.answer(config, policy, seed, dtype, cell)
    out["makespan"] = float(np.nanmax(out["finish"]))
    return out
'''


@pytest.fixture
def cpu_runs(tmp_path, monkeypatch):
    """Whole runs driven on the CPU: only the look for a chip is skipped."""
    # keep the persistent compile cache out of the checkout in tests
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    from harness import device, runner
    monkeypatch.setattr(runner, "require_accelerator", device.describe)


@pytest.fixture
def bench_root(tmp_path, cpu_runs):
    """A checkout-shaped directory whose ``BENCHMARK.json`` names three new
    cells: one with its own configuration, traffic mix, limits and a new
    per-layer metric as files of their own, beside copies of the
    benchmark's end-to-end readers; one that runs the grid's traffic file
    and check rule on a tiny job-type deployment; and one whose
    configuration sets a cluster key (a quiet fault layer) that only the
    reference it names models.  No file of the benchmark is edited."""
    root = tmp_path / "checkout"
    for sub in ("configs", "traffic", "metrics", "checks"):
        (root / "bench" / sub).mkdir(parents=True)
    for name in ("setup_s", "query_p50_s"):
        shutil.copy(BENCH / "metrics" / f"{name}.py",
                    root / "bench" / "metrics")
    config = json.loads((BENCH / "configs" / "paper_20x2.json").read_text())
    config["num_jobs"] = 12
    (root / "bench" / "configs" / "tiny_20x2.json").write_text(
        json.dumps(config))
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps({
        "why": "two columns x two fresh seeds",
        "policies": [{"name": "fair", "params": {}},
                     {"name": "proposed", "params": {"max_wait": 20.0}}],
        "draw": None, "fresh_seeds": 2, "check_cells": 100,
        "trace_requests": 1}))
    shutil.copy(BENCH / "checks" / "query.paper_20x2.json",
                root / "bench" / "checks" / f"{TINY_CELL}.json")
    types = json.loads((BENCH / "configs" / "fb2009_600x2.json").read_text())
    types["num_jobs"] = 12
    types["cluster"]["num_machines"] = 20
    (root / "bench" / "configs" / "tinytypes_20x2.json").write_text(
        json.dumps(types))
    shutil.copy(BENCH / "traffic" / "grid.json", root / "bench" / "traffic")
    shutil.copy(BENCH / "checks" / "grid.fb2009_600x2.json",
                root / "bench" / "checks" / f"{GRID_CELL}.json")
    keyed = dict(config, reference=KEYED_REFERENCE)
    keyed["cluster"] = dict(config["cluster"], faults={"enabled": True})
    (root / "bench" / "configs" / "keyed_20x2.json").write_text(
        json.dumps(keyed))
    (root / KEYED_REFERENCE).parent.mkdir(parents=True)
    (root / KEYED_REFERENCE).write_text(
        '"""The default model; a quiet fault layer changes nothing."""\n'
        "from harness.reference import CLUSTER_KEYS as DEFAULT_KEYS\n"
        "from harness.reference import answer, build, deadline, lower\n"
        'CLUSTER_KEYS = DEFAULT_KEYS + ("faults",)\n')
    shutil.copy(BENCH / "checks" / "query.paper_20x2.json",
                root / "bench" / "checks" / f"{KEYED_CELL}.json")
    (root / "bench" / "metrics" / "tiny_requests.py").write_text(
        "def read(ctx):\n    return float(len(ctx.served))\n")
    (root / "BENCHMARK.json").write_text(json.dumps({
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny_20x2", "source": "fixture",
                     "file": "bench/configs/tiny_20x2.json",
                     "reduced": ["num_jobs"], "why": "fixture"},
                    {"name": "tinytypes_20x2", "source": "fixture",
                     "file": "bench/configs/tinytypes_20x2.json",
                     "reduced": ["num_jobs", "num_machines"],
                     "why": "fixture"},
                    {"name": "keyed_20x2", "source": "fixture",
                     "file": "bench/configs/keyed_20x2.json",
                     "reduced": ["num_jobs"], "why": "fixture"}],
        "workloads": [{"name": TINY_CELL, "config": "tiny_20x2",
                       "traffic": "tiny", "chips": 1, "why": "fixture"},
                      {"name": GRID_CELL, "config": "tinytypes_20x2",
                       "traffic": "grid", "chips": 1, "why": "fixture"},
                      {"name": KEYED_CELL, "config": "keyed_20x2",
                       "traffic": "tiny", "chips": 1, "why": "fixture"}],
        "end_to_end": [
            {"name": "query_p50_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "tiny_requests", "unit": "requests",
             "better": "higher", "source": "host_clock", "layer": "fixture",
             "moves": "query_p50_s"}]}))
    return root


@pytest.fixture
def extended_root(tmp_path, cpu_runs):
    """A copy of the real ``BENCHMARK.json`` and ``bench/`` to which a
    deployment is added as a ``model_config`` change adds one, as new files
    and appended entries only: a configuration at fb2009_600x2's fleet
    (``num_jobs`` cut to 12) naming a reference that models a quiet
    ``faults`` group and adds the cell's makespan to the check, a
    ``grid``-traffic workload, its checks file with the extra limit, a
    per-layer reader with a ``workloads`` list, and the cell appended to
    ``cells_per_s``'s ``workloads``.  No copied file but
    ``BENCHMARK.json`` changes, and it only gains entries."""
    root = tmp_path / "extended"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    config = json.loads((BENCH / "configs" / "fb2009_600x2.json").read_text())
    config["num_jobs"] = 12
    config["reference"] = PROBE_REFERENCE
    config["cluster"]["faults"] = {"enabled": True}
    (root / "bench" / "configs" / f"{PROBE_CONFIG}.json").write_text(
        json.dumps(config))
    (root / PROBE_REFERENCE).parent.mkdir(exist_ok=True)
    (root / PROBE_REFERENCE).write_text(PROBE_REFERENCE_TEXT)
    checks = json.loads(
        (BENCH / "checks" / "grid.fb2009_600x2.json").read_text())
    checks["limits"]["makespan_max_abs"] = 30
    (root / "bench" / "checks" / f"{PROBE_CELL}.json").write_text(
        json.dumps(checks))
    (root / "bench" / "metrics" / f"{PROBE_METRIC}.py").write_text(
        "def read(ctx):\n    return float(len(ctx.served))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": PROBE_CONFIG, "source": "fixture",
        "file": f"bench/configs/{PROBE_CONFIG}.json",
        "reduced": ["num_jobs"], "why": "fixture"})
    bench["workloads"].append({"name": PROBE_CELL, "config": PROBE_CONFIG,
                               "traffic": "grid", "chips": 1,
                               "why": "fixture"})
    for m in bench["end_to_end"]:
        if m["name"] == "cells_per_s":
            m["workloads"].append(PROBE_CELL)
    bench["per_layer"].append({
        "name": PROBE_METRIC, "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "fixture", "moves": "cells_per_s",
        "workloads": [PROBE_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(params=["real", "extended"])
def benchmark_root(request):
    """The real checkout root, then the same extended by ``extended_root``."""
    if request.param == "real":
        return ROOT
    return request.getfixturevalue("extended_root")
