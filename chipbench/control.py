"""The control of `correct`: the plain reference put in the program's
place, with its distances computed one precision below the
configuration's (bfloat16 for float32), held against the float32
reference through the comparison a run uses. It has to come out as not
correct; its readings are the upper ends the limits were set under.

    python chipbench/control.py --workload <cell> --seeds 1,2,3 [--jobs 3]

Runs no job of the program and needs no timed window: the check the
configuration names (`checks/<reference.kind>.py`, its `control_numbers`)
draws the cell's rows for each seed at the cell's own size, samples the
test rows as a run of `--jobs` jobs does, and this prints one JSON line per
seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import compare, manifest  # noqa: E402


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args(argv)
    man = manifest.Manifest()
    cell = man.cell(args.workload)
    check = man.module("checks", cell.config["reference"]["kind"])
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers = check.control_numbers(cell, seed, args.jobs, args.dtype)
        good, _rows = compare.verdict(numbers, cell.config["check"]["limits"])
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "dtype": args.dtype, "correct": good,
                          "numbers": numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
