"""The engine against the benchmark's plain reference, per algorithm.

`bench/refs/difet_features.py` is the independent reference that decides
a benchmark run's ``correct``, by the rule and limits of
`bench/compare.py`.  Here the same rule holds `extract_features_multi`
to it at the geometries the cells run, plus the two edge cases a tile can
be: a scene corner with reflect padding, and a flat tile with nothing to
find.  Each geometry is extracted once, with all seven algorithms."""
import dataclasses
import functools
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from bench import compare  # noqa: E402
from bench.refs import difet_features as ref  # noqa: E402
from repro.configs.difet_paper import PAPER_ALGORITHMS, DifetConfig  # noqa: E402
from repro.core.bundle import tile_scene  # noqa: E402
from repro.core.engine import extract_features_multi  # noqa: E402
from repro.data.landsat import synthetic_scene  # noqa: E402
from repro.serve.buckets import BucketTable  # noqa: E402

# the limits both benchmark configurations state
LIMITS = {"count_gap": 0.01, "keypoint_miss": 0.01, "desc_miss": 0.01}
JOB = DifetConfig(tile=512, halo=24, max_keypoints_per_tile=512)
SERVICE = DifetConfig(tile=256, halo=16, max_keypoints_per_tile=128)


def _job_tiles(pick):
    """One tile of a 3 x 3-tile scene cut as the job cuts it."""
    b = tile_scene(synthetic_scene(1200, 1250, seed=5, density=4.0), JOB)
    i = pick(b.headers)
    return b.tiles[i:i + 1], b.headers[i:i + 1]


def _bucket_tiles(gray):
    tile, header = BucketTable([256], SERVICE).pad_to_bucket(gray, 256)
    return tile[None], header[None]


GEOMETRIES = {
    # interior tile of the job: 512 + halo 24, K 512, neighbours in the halo
    "job_tile": (JOB, lambda: _job_tiles(
        lambda h: int(np.flatnonzero((h[:, 1] == 1) & (h[:, 2] == 1))[0]))),
    # the service's bucket: a 256-px request padded to 256 + halo 16, K 128
    "service_bucket": (SERVICE, lambda: _bucket_tiles(
        synthetic_scene(256, 256, seed=4, density=4.0))),
    # the scene's bottom-right tile: 176 x 226 valid, reflect-padded
    "scene_corner": (JOB, lambda: _job_tiles(lambda h: len(h) - 1)),
    # a flat tile: no response passes any threshold
    "flat": (SERVICE, lambda: _bucket_tiles(
        np.full((256, 256), 0.5, np.float32))),
}


@pytest.fixture(scope="module", params=list(GEOMETRIES))
def geometry(request):
    """``(name, {alg: (program's answer, reference's answer)})``."""
    cfg, make = GEOMETRIES[request.param]
    tiles, headers = make()
    got = jax.device_get(jax.jit(functools.partial(
        extract_features_multi, algorithms=PAPER_ALGORITHMS, cfg=cfg))(
        tiles, headers))
    want = ref.features(tiles, headers, dataclasses.asdict(cfg),
                        PAPER_ALGORITHMS, "float32", block=len(tiles))
    k = cfg.max_keypoints_per_tile
    return request.param, {a: (got[a], ref.job_result(want[a], k))
                           for a in PAPER_ALGORITHMS}


@pytest.mark.parametrize("alg", PAPER_ALGORITHMS)
def test_engine_matches_reference(geometry, alg):
    name, pairs = geometry
    got, want = pairs[alg]
    tally = compare.tally([(alg, got, want)])
    ok, checks = compare.checks(tally.numbers(), LIMITS)
    assert ok, checks
    if name == "flat":
        assert int(want["total_count"]) == int(got["total_count"]) == 0
        assert not np.any(got["top_valid"]) and not np.any(want["top_valid"])
    else:
        assert tally.keypoints() > 0, "nothing compared"
