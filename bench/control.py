#!/usr/bin/env python3
"""The control of a cell's correctness check, at the cell's own size.

Puts the cell's plain reference computed in bfloat16 in the program's
place and compares it, number by number, with the float32 reference on a
sample of the cell's own request stream, as a run's check would.  A sound
check calls it not correct.  It needs no chip (the reference is numpy);
the benchmark's runs never run it.  Usage::

    python3 bench/control.py --workload <cell> --seed <n> [--requests 4]

Prints the numbers beside the cell's limits and the verdict as one JSON
line.
"""
import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import ml_dtypes  # noqa: E402

from harness import check, program, registry  # noqa: E402
from harness.traffic import Stream  # noqa: E402


def control_numbers(workload, seed, requests, root=registry.ROOT):
    cell = registry.find_cell(workload, root)
    R = cell.reference
    conf = dict(cell.config)
    conf["trace"] = program.trace_recipe(cell.config)
    stream = Stream(cell.traffic, workload, seed)
    cells, groups = [], []
    for k in range(requests):
        batch = program.request_cells(cell.config, stream.request(k))
        groups.append(list(range(len(cells), len(cells) + len(batch))))
        cells.extend(batch)
    picked = check.sample(groups, [0.0] * len(cells), cell.traffic,
                          workload, seed)
    answers, refs = [], []
    for i in picked:
        policy, s = cells[i]
        built = R.build(conf, policy, s)
        refs.append(R.answer(conf, policy, s, cell=built))
        answers.append(R.answer(conf, policy, s, dtype=ml_dtypes.bfloat16,
                                cell=built))
    extras = check.extra(R)
    return (check.compare(answers, refs, extras),
            check.limits(root, workload, check.names(extras)))


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=4)
    args = ap.parse_args()
    numbers, limits = control_numbers(args.workload, args.seed,
                                      args.requests)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "control_correct": check.verdict(numbers, limits),
                      "compared": check.report(numbers, limits)}))


if __name__ == "__main__":
    main()
