#!/usr/bin/env python3
"""Readings of a cell's correctness numbers for the program and for its
control, on several seeds in one process.

    python bench/control.py --workload metric_cluster.search --seconds 5 \\
        --seeds 11,12,13

For each seed it runs the cell once (a short window at the cell's own
load), then compares what the timed path produced with the plain
reference, and compares the reference computed one precision step lower
(the control) in the program's place too. One JSON line per seed. The
limits in the configuration files were set from these readings: above
the largest the program gives and below the smallest the control gives.
Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        r = run_cell.run(args.workload, seed, args.seconds, False,
                         readings=True)
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "control_correct": r["control_correct"],
                          "metrics": r["metrics"], "checks": r["checks"],
                          "control_checks": r["control_checks"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
