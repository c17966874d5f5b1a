#!/usr/bin/env python3
"""Benchmark of the scheduler what-if engine on the chip.

Runs one cell of ``BENCHMARK.json`` and prints its result as the last line
of standard output::

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, where JAX finds no accelerator or
fewer chips than the cell asks for.
"""
import sys
import time

T0 = time.perf_counter()

from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

from harness.runner import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t0=T0))
