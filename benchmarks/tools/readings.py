#!/usr/bin/env python3
"""Readings of the controls: each query's reference computed in each of
``harness/lowprec.py``'s bfloat16 precisions (``control`` of its file), put
in the program's place and held to the float64 reference by the comparison
that decides ``correct``, for the first parameter sets a run of that seed
draws.  The limits of ``queries/<name>.py`` stand between the program's
readings (every run's ``compared``) and these.  The benchmark's own runs
never run this.

    python3 benchmarks/tools/readings.py --workload <name> --seeds 1,2,3 [--sets 2] [--rehearse]
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from harness import check, loadgen, lowprec, spec, tables  # noqa: E402


def control_numbers(cell, paths, seed, n_sets) -> dict:
    out = {}
    sets = loadgen.plan(cell.traffic, seed)
    for name, query in cell.queries.items():
        for precision in lowprec.CONTROLS:
            numbers = {}
            for params in sets[name][:n_sets]:
                found, _ = check.compare(
                    [query.control(paths, params, precision)],
                    query.reference(paths, params),
                    query.SORT_KEYS, query.EXACT)
                check.merge(numbers, found)
            out[f"{name}.{precision.name}"] = dict(
                numbers, fails=not check.judge(numbers, query.LIMITS))
    return out


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.Cell(args.workload)
    for seed in map(int, args.seeds.split(",")):
        paths = tables.for_cell(cell, seed, args.rehearse)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": control_numbers(cell, paths, seed,
                                                     args.sets)}),
              flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
