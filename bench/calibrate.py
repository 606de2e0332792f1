"""Readings for a cell's correctness limits: the compared numbers of the
program over many seeds, and of the control (the plain reference in
bfloat16 put in the program's place) over a few, all in one process.

    python3 bench/calibrate.py --workload landsat8.all7.x1 \
        --seeds 11,12,13 --control-seeds 21,22,23 --seconds 5

Each run is a whole run of the cell (set-up, a short window at the cell's
own load, the check); one line of JSON per run: its kind, seed, correct
and each number with its worst algorithm."""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

if __name__ == "__main__":
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    runs = [("program", s) for s in args.seeds.split(",") if s] + \
        [("control", s) for s in args.control_seeds.split(",") if s]
    for kind, seed in runs:
        result, run = harness.run_cell(args.workload, int(seed),
                                       args.seconds, False,
                                       control=kind == "control")
        print(json.dumps({"kind": kind, "seed": int(seed),
                          "correct": result["correct"],
                          "attempted": result["attempted"],
                          "numbers": run["numbers"],
                          "metrics": result["metrics"]}), flush=True)
