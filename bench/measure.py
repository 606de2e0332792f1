"""Measure a cell's spread the way its bounds are set: two sets of runs
with the same seeds, then traced runs, each run a process of its own.

    python3 bench/measure.py --workload landsat8.all7.x1 \
        --seeds 11,12,13,14,15,16 --sets 2 --traced 21,22,23 \
        --out runs/x1.json

This process never touches JAX (each run holds the chips alone).  Per
set and metric it prints the median and the spread: the distance between
the first and third quartiles of ``statistics.quantiles(values, n=4)``
as a share of the median.  Every run's result line, its exit code and
its check lines go to ``--out``."""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def one(workload, seed, seconds, trace) -> dict:
    t0 = time.time()
    p = subprocess.run([sys.executable, str(ROOT / "bench/run.py"),
                        "--workload", workload, "--seed", str(seed),
                        "--seconds", str(seconds), "--trace", str(trace)],
                       cwd=ROOT, capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1])
    except (IndexError, ValueError):
        result = None
    return {"seed": seed, "trace": trace, "rc": p.returncode,
            "wall_s": time.time() - t0, "result": result,
            "log": [x for x in lines[:-1] if not x.startswith("{")][-12:],
            "stderr": p.stderr[-1500:]}


def spread(values) -> tuple:
    q1, med, q3 = statistics.quantiles(values, n=4)
    return statistics.median(values), (q3 - q1) / statistics.median(values)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced", default="")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    seconds = args.seconds or json.loads(
        (ROOT / "BENCHMARK.json").read_text())["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    runs = []
    for k in range(args.sets):
        for s in seeds:
            r = one(args.workload, s, seconds, 0)
            r["set"] = k
            runs.append(r)
            res = r["result"] or {}
            print(json.dumps({"set": k, "seed": s, "rc": r["rc"],
                              "correct": res.get("correct"),
                              **{m: v["value"] for m, v in
                                 res.get("metrics", {}).items()}}),
                  flush=True)
    for s in (int(x) for x in args.traced.split(",") if x):
        r = one(args.workload, s, seconds, 1)
        runs.append(r)
        res = r["result"] or {}
        print(json.dumps({"traced": s, "rc": r["rc"],
                          "correct": res.get("correct"),
                          **{m: v["value"] for m, v in
                             res.get("metrics", {}).items()},
                          "device": res.get("device")}), flush=True)
    summary = {}
    for k in range(args.sets):
        got = [r["result"] for r in runs
               if r.get("set") == k and r["result"]]
        for m in (got[0]["metrics"] if got else {}):
            vals = [g["metrics"][m]["value"] for g in got]
            if len(vals) >= 2:
                med, sp = spread(vals)
                summary[f"set{k}.{m}"] = {"median": med, "spread": sp,
                                          "values": vals}
    print(json.dumps(summary), flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps({"runs": runs,
                                          "summary": summary}, indent=1))
