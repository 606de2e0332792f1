"""The four-chip job cell runs through the harness on four virtual CPU
devices: its files load, the job shards each bundle over a 2x2 mesh, and
the run is correct."""
import os
import subprocess
import sys

from bench.spec import ROOT

X4 = r"""
import sys
sys.path[:0] = [{root!r}, {root!r} + "/src"]
import jax
from bench.tests.faults import run_small
from pathlib import Path
assert len(jax.devices()) == 4
r, run = run_small("landsat8.all7.x4", Path(sys.argv[1]),
                   overrides={{"config": {{"bundle_tiles": 8}}}})
print("RESULT", r["correct"], run["chips"], run["commits"],
      sorted(r["metrics"]))
"""


def test_four_chip_cell_runs_on_four_devices(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run(
        [sys.executable, "-c", X4.format(root=str(ROOT)), str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600)
    line = [x for x in out.stdout.splitlines() if x.startswith("RESULT")]
    assert line, out.stderr[-3000:]
    correct, chips, commits, metrics = line[0].split(" ", 4)[1:]
    assert correct == "True" and chips == "4" and int(commits) > 0
    assert "scene_mpx_per_chip_s" in metrics and "setup_s" in metrics
