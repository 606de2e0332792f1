"""Shared by the fault tests: runs of the harness at sizes a CPU holds,
past the harness's look for a chip."""
from bench import harness

SMALL = {
    "landsat8": {"config": {"scene_hw": [200, 260], "tile": 64,
                            "bundle_tiles": 4, "max_keypoints_per_tile": 64},
                 "traffic": {"items_per_second": 100}},
    "xyz256": {"config": {"buckets": [64], "max_keypoints_per_tile": 32},
               "traffic": {"rate": 60.0, "prefix_requests": 16,
                           "check_requests": 60, "pool_size": 96}},
}
SEED = 2 ** 31 + 977


def run_small(cell, tmp_path, seconds=1.5, control=False, overrides=None):
    """(result, run record) of one run at a small size."""
    ov = {k: dict(v) for k, v in SMALL[cell.split(".")[0]].items()}
    for part, keys in (overrides or {}).items():
        ov[part] = {**ov.get(part, {}), **keys}
    return harness.run_cell(cell, SEED, seconds, False, require_tpu=False,
                            overrides=ov, control=control)
