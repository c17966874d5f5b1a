"""Shared set-up of the benchmark's CPU tests: the harness on the import
path, and a checkout-shaped fixture root that holds a tiny cell of its
own."""
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


@pytest.fixture
def bench_root(tmp_path, monkeypatch):
    """A checkout-shaped directory whose ``BENCHMARK.json`` names three new
    cells: one with its own configuration, traffic mix, limits and a new
    per-layer metric as files of their own, beside copies of the
    benchmark's end-to-end readers; one that runs the grid's traffic file
    and check rule on a tiny job-type deployment; and one whose
    configuration sets a cluster key (a quiet fault layer) that only the
    reference it names models.  No file of the benchmark is edited."""
    # keep the persistent compile cache out of the checkout in tests
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    # the rest of a run is driven on the CPU: skip only the look for a chip
    from harness import device, runner
    monkeypatch.setattr(runner, "require_accelerator", device.describe)
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
