#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from; not part of a
benchmark run.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 5 [--fault <name>]

For each seed, in one process: the cell's set-up and a short window at
its own load, then the numbers `correct` compares, for the program's
answers and for the control's (the reference in bfloat16 in the
program's place, over the same sampled requests). With `--fault`, the
program runs with that fault planted (`faults.py`) and the control is
not read. One JSON line per seed on standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import faults  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)
    if args.fault:
        faults.plant(args.fault)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            r = run.run_cell(args.workload, seed, args.seconds, False,
                             control=not args.fault)
        except run.NoChip as e:
            print(str(e), file=sys.stderr)
            return 1
        print(json.dumps({
            "workload": args.workload, "seed": seed, "fault": args.fault,
            "correct": r["correct"], "requests": r["window"]["requests"],
            "program": dict({n: c["value"] for n, c in r["checks"].items()},
                            **r["readings"]),
            "control": r.get("control")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
