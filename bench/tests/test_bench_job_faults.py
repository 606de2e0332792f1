"""The batch cells' check: a sound run is correct; the bfloat16 control
and each fault the job can have, planted in the timed path, are not."""
import os
import subprocess
import sys

import pytest

from bench.spec import ROOT
from bench.tests.faults import run_small


def test_sound_run_is_correct(tmp_path):
    r, run = run_small("landsat8.all7.x1", tmp_path)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r["checks"]) == ["count_gap", "keypoint_miss", "desc_miss"]
    assert "scene_mpx_per_chip_s" in r["metrics"]
    # the compared commits hold keypoints; how many commits the window
    # gets depends on the machine's speed, so count per commit
    assert run["checked_commits"] > 0 and run["window_compiles"] == 0
    assert run["keypoints"] >= 10 * run["checked_commits"]


def test_control_in_bfloat16_is_not_correct(tmp_path):
    r, run = run_small("landsat8.all7.x1", tmp_path, control=True)
    assert not r["correct"], r["checks"]


def test_altered_answer_is_caught(tmp_path, monkeypatch):
    from repro.core import job
    real = job.reduce_features_multi

    def altered(per_tile):        # every keypoint one row off
        out = real(per_tile)
        return {a: {**r, "top_ys": r["top_ys"] + 1} for a, r in out.items()}
    monkeypatch.setattr(job, "reduce_features_multi", altered)
    r, run = run_small("landsat8.all7.x1", tmp_path)
    assert not r["correct"]
    assert r["checks"]["keypoint_miss"]["value"] > 0.5


def test_half_the_batch_left_out_is_caught(tmp_path, monkeypatch):
    from repro.core import job
    real = job.map_tiles_multi

    def half(tiles, headers, **kw):   # the second half never extracted
        n = tiles.shape[0] // 2
        pad = headers.at[n:, 5].set(1)
        return real(tiles, pad, **kw)
    monkeypatch.setattr(job, "map_tiles_multi", half)
    r, run = run_small("landsat8.all7.x1", tmp_path)
    assert not r["correct"]
    assert r["checks"]["count_gap"]["value"] > 0.2


EXCHANGE = r"""
import sys
sys.path[:0] = [{root!r}, {root!r} + "/src"]
import jax
from repro.core import job
from bench.tests.faults import run_small
from pathlib import Path
assert len(jax.devices()) == 4
fault = sys.argv[1] == "fault"
if fault:
    real = job.reduce_features_multi
    def local_only(per_tile):   # each device reduces its own shard alone
        n = jax.tree_util.tree_leaves(per_tile)[0].shape[0] // 4
        return real(jax.tree_util.tree_map(lambda a: a[:n], per_tile))
    job.reduce_features_multi = local_only
r, run = run_small("landsat8.all7.x1", Path(sys.argv[2]),
              overrides={{"workload": {{"chips": 4}},
                         "config": {{"bundle_tiles": 8}}}})
print("CORRECT", r["correct"], r["checks"]["count_gap"]["value"])
"""


@pytest.mark.parametrize("fault", ["sound", "fault"])
def test_exchange_between_chips_left_out_is_caught(tmp_path, fault):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run(
        [sys.executable, "-c", EXCHANGE.format(root=str(ROOT)), fault,
         str(tmp_path)], env=env, capture_output=True, text=True,
        timeout=600)
    line = [x for x in out.stdout.splitlines() if x.startswith("CORRECT")]
    assert line, out.stderr[-3000:]
    assert line[0].split()[1] == ("True" if fault == "sound" else "False")
