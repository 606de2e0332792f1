"""Find a service cell's knee: the highest offered rate at which the
queue does not grow over the window.

    python3 bench/sweep.py --workload xyz256.steady --rates 50,100,200 \
        --seconds 8 --seed 7

Runs the cell once per rate in this process (so the programs compile or
load once) and prints per rate the latency median and 95th percentile,
the median latency of the last fifth of the requests over that of the
first fifth (a queue that grows through the window drives it up), batch
occupancy and missing answers.  One line of JSON per rate."""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

if __name__ == "__main__":
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    for rate in (float(r) for r in args.rates.split(",")):
        result, run = harness.run_cell(
            args.workload, args.seed, args.seconds, False,
            overrides={"traffic": {"rate": rate}})
        st = run["stats"]
        print(json.dumps({
            "rate": rate, "correct": result["correct"],
            "failed": result["failed"],
            "p50_ms": result["metrics"]["tile_p50_ms"]["value"],
            "p95_ms": float(np.percentile(run["latency_s"], 95)) * 1e3,
            "growth": run["growth"],
            "occupancy": st["items"] / max(st["batches"], 1)
            / run["max_batch"],
            "late_p99_ms": sorted(run["late_s"])[int(0.99 * len(
                run["late_s"]))] * 1e3}), flush=True)
