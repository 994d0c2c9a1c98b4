#!/usr/bin/env python3
"""The control of a cell's comparison, run through the harness: a whole run
(inputs from each seed, warm-up, a short window at the cell's own sizes,
the comparison) with the control entry (bench/entries/control.py, the
float64 reference computed in bfloat16) in the program's place.  Each seed
has to read `correct` false; bench/run.py's own runs never use it.

  python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 1]

Prints one JSON line per seed and exits 1 if any seed reads correct.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    sys.path.insert(0, run.BENCH)
    cell = run.load_cell(args.workload)
    caught = True
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.measure(cell, seed, args.seconds, False, entry="control")
        caught &= res["correct"] is False
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "checks": res["checks"]}), flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
