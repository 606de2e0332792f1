"""The fleet cell runs through the harness on four virtual CPU devices:
its files load, four thread replicas serve from four distinct devices
behind the router, every replica answers checked requests, the run is
correct, and the fleet's counters reach its metric readers."""
import json
import os
import subprocess
import sys

from bench.spec import ROOT

FLEET4 = r"""
import json, sys
sys.path[:0] = [{root!r}, {root!r} + "/src"]
import jax
from bench.spec import Bench
from bench.tests.faults import run_small
from pathlib import Path
assert len(jax.devices()) == 4
r, run = run_small("xyz256.fleet4", Path(sys.argv[1]))
b = Bench()
print("RESULT", json.dumps({{
    "correct": r["correct"], "failed": r["failed"],
    "attempted": r["attempted"], "metrics": sorted(r["metrics"]),
    "chips": run["chips"], "devices": run["replica_devices"],
    "items": run["replica_items"],
    "read": {{m: b.module("metrics", m).read(run) for m in (
        "tile_p50_ms", "router_ms_per_request.fleet",
        "replica_imbalance.fleet", "batch_occupancy.tile",
        "device_ms_per_step.fleet")}}}}))
"""


def test_fleet_cell_runs_on_four_devices(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run(
        [sys.executable, "-c", FLEET4.format(root=str(ROOT)), str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600)
    line = [x for x in out.stdout.splitlines() if x.startswith("RESULT ")]
    assert line, out.stderr[-3000:]
    got = json.loads(line[0][len("RESULT "):])
    assert got["correct"], out.stdout[-3000:]
    assert got["chips"] == 4 and len(set(got["devices"].values())) == 4
    assert len(got["items"]) == 4 and min(got["items"].values()) > 0
    assert got["failed"] == 0 and got["attempted"] == round(60.0 * 1.5)
    assert {"tile_p50_ms", "setup_s"} <= set(got["metrics"])
    read = got["read"]
    for m in ("tile_p50_ms", "router_ms_per_request.fleet",
              "replica_imbalance.fleet", "batch_occupancy.tile"):
        assert read[m] is not None and read[m] > 0, (m, read)
    assert read["replica_imbalance.fleet"] >= 1.0
    assert read["device_ms_per_step.fleet"] is None     # no trace on a CPU
