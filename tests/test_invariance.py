"""Answers that must not depend on where a tile rides or where it is kept.

* A tile's features are the same bits alone and at any position of a
  batch of other tiles: the job packs 64 tiles a bundle, the service up
  to ``max_batch`` requests a step, and the four-chip job 16 a chip, so a
  reduction whose order the backend picks by shape would round a tile
  differently in each (`pyramid.integral_image` and
  `descriptors._histogram` add in a fixed order for this).
* A result written by `BundleStore.put_result` reads back bit for bit,
  with its dtype: BRIEF and ORB pack their bits into ``uint32`` words,
  SIFT and SURF descriptors are float32, the detectors carry none."""
import functools

import jax
import numpy as np
import pytest

from repro.configs.difet_paper import PAPER_ALGORITHMS, DifetConfig
from repro.core.bundle import BundleStore, tile_scene
from repro.core.engine import extract_features_multi, map_tiles_multi
from repro.data.landsat import synthetic_scene

CFG = DifetConfig(tile=128, halo=16, max_keypoints_per_tile=64)
POS = 5                     # the tile's place in the batch of 8
BINARY = {"brief", "orb"}
FLOAT_DESC = {"sift", "surf"}


@pytest.fixture(scope="module")
def batch():
    """Eight different tiles of one scene (2 x 4 tiles of 128 px)."""
    b = tile_scene(synthetic_scene(256, 512, seed=11, density=4.0), CFG)
    assert len(b) == 8
    return b.tiles, b.headers


@pytest.fixture(scope="module")
def per_tile(batch):
    """The per-tile map of every algorithm, jitted as the job and the
    service run it: over the batch of 8, and over tile ``POS`` alone."""
    tiles, headers = batch
    fn = jax.jit(functools.partial(map_tiles_multi,
                                   algorithms=PAPER_ALGORITHMS, cfg=CFG))
    return (jax.device_get(fn(tiles, headers)),
            jax.device_get(fn(tiles[POS:POS + 1], headers[POS:POS + 1])))


@pytest.fixture(scope="module")
def reduced(batch):
    tiles, headers = batch
    return jax.device_get(jax.jit(functools.partial(
        extract_features_multi, algorithms=PAPER_ALGORITHMS,
        cfg=CFG))(tiles, headers))


@pytest.mark.parametrize("alg", PAPER_ALGORITHMS)
def test_tile_features_do_not_depend_on_batch_position(per_tile, alg):
    in_batch, alone = per_tile
    assert in_batch[alg].keys() == alone[alg].keys()
    assert alone[alg]["valid"][0].any(), "the tile has no keypoint"
    for k in alone[alg]:
        np.testing.assert_array_equal(in_batch[alg][k][POS], alone[alg][k][0],
                                      err_msg=f"{alg}.{k}")


@pytest.mark.parametrize("alg", PAPER_ALGORITHMS)
def test_store_result_round_trip(tmp_path, reduced, alg):
    store = BundleStore(tmp_path)
    res = {k: np.asarray(v) for k, v in reduced[alg].items()}
    assert res["top_valid"].any()
    store.put_result(f"b0.{alg}", res)
    assert store.has_result(f"b0.{alg}")
    back = store.get_result(f"b0.{alg}")
    assert back.keys() == res.keys()
    for k in res:
        assert back[k].dtype == res[k].dtype, k
        np.testing.assert_array_equal(back[k], res[k], err_msg=k)
    if alg in BINARY:
        assert back["top_desc"].dtype == np.uint32
        assert back["top_desc"].shape[1:] == (8,)       # 256 bits
    elif alg in FLOAT_DESC:
        assert back["top_desc"].dtype == np.float32
    else:
        assert "top_desc" not in back
