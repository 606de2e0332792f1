"""The benchmark's generators: the same seed gives the same bytes, and
the configured sizes come out."""
import numpy as np
import pytest

from bench import gen, scene
from bench.spec import Bench


def test_scene_same_bytes_for_same_seed():
    a = scene.synthetic_scene(300, 257, seed=scene.sub_seed(2 ** 31 + 7, 0))
    b = scene.synthetic_scene(300, 257, seed=scene.sub_seed(2 ** 31 + 7, 0))
    c = scene.synthetic_scene(300, 257, seed=scene.sub_seed(2 ** 31 + 8, 0))
    assert a.dtype == np.float32 and a.tobytes() == b.tobytes()
    assert a.tobytes() != c.tobytes()


def test_paper_scene_cuts_into_256_tiles_of_560():
    c = Bench().config("landsat8_paper")
    h, w = c["scene_hw"]
    tiles, headers = scene.tile_scene(np.zeros((h, w), np.float32),
                                      c["tile"], c["halo"])
    assert tiles.shape == (256, 560, 560)
    parts = scene.bundles(tiles, headers, c["bundle_tiles"])
    assert [p[0].shape for p in parts] == [(64, 560, 560)] * 4
    assert sum(scene.interior_px(p[1]) for p in parts) == h * w


def test_tile_scene_matches_reflect_padding():
    img = scene.synthetic_scene(100, 90, seed=3)
    tiles, headers = scene.tile_scene(img, 64, 8)
    assert tiles.shape == (4, 80, 80)
    padded = np.pad(img, ((8, 8 + 28), (8, 8 + 38)), mode="reflect")
    np.testing.assert_array_equal(tiles[3], padded[64:144, 64:144])
    assert headers[3].tolist() == [0, 1, 1, 36, 26, 0]


def _sched(mix, seed, seconds=5.0, pool=4096, first=0):
    return gen.schedule(mix, seconds, pool,
                        np.random.RandomState(scene.sub_seed(seed, 0)),
                        first_tile=first)


def test_schedule_same_for_same_seed_same_work_for_any():
    mix = Bench().traffic("xyz256.steady")
    a, b = _sched(mix, 11, pool=10 ** 5), _sched(mix, 11, pool=10 ** 5)
    c = _sched(mix, 12, pool=10 ** 5)
    assert a == b and a != c
    assert len(a) == len(c) == round(mix["rate"] * 5.0)
    for s in (a, c):
        sets = [r.algorithms for r in s]
        for mix_set, w in zip(mix["algorithm_sets"], mix["algorithm_weights"]):
            assert sets.count(tuple(mix_set)) == round(w * len(s))
        assert len({r.tile for r in s}) == len(s)      # every tile unique
        assert 0 <= s[0].t and s[-1].t < 5.0


HOT = {"arrival": "poisson", "rate": 288.0, "algorithm_sets": [["sift"]],
       "algorithm_weights": [1.0], "pool_size": 4096, "hot_fraction": 0.125,
       "hot_weight": 0.7}


def test_hot_mix_pool_and_share():
    mix = HOT
    s = _sched(mix, 5, pool=mix["pool_size"])
    n_hot = int(np.ceil(mix["hot_fraction"] * mix["pool_size"]))
    assert n_hot == 512
    hot = sum(r.tile < n_hot for r in s)
    assert hot == round(mix["hot_weight"] * len(s))
    assert max(r.tile for r in s) < 4096


def test_window_pool_tiles_are_distinct():
    img = scene.synthetic_scene(512, 512, seed=1)
    pool = gen.WindowPool(img, 256, 200, 16, np.random.RandomState(0))
    digests = {pool.tile(i).tobytes() for i in range(len(pool))}
    assert len(digests) == 200
    assert pool.tile(0).shape == (256, 256)


@pytest.mark.parametrize("kind", ["uniform", "poisson", "burst"])
def test_arrivals_span_the_window(kind):
    t = gen.arrivals(500, 10.0, kind, np.random.RandomState(0))
    assert len(t) == 500 and t[0] == 0 and t[-1] < 10.0
    assert np.all(np.diff(t) >= 0)


def test_burst_arrivals_come_in_runs_at_four_times_the_rate():
    t = gen.arrivals(800, 10.0, "burst", np.random.RandomState(3))
    gaps = np.diff(np.append(t, 10.0))
    fast = np.isclose(gaps, gaps.min())
    assert np.all(fast | np.isclose(gaps, gaps.max()))  # two gap lengths
    # burst gaps are a quarter of the mean, calm ones 1.25 of it (factor
    # 4 on a quarter of the requests keeps the mean rate)
    np.testing.assert_allclose(gaps.max() / gaps.min(), 5.0)
    assert 0.2 < fast.mean() < 0.5
    assert fast.reshape(-1, 8).all(1).sum() >= 10    # whole runs of eight


def test_every_seed_gets_the_same_gaps():
    a, b = (np.diff(np.append(gen.arrivals(400, 10.0, "poisson",
                                           np.random.RandomState(s)), 10.0))
            for s in (1, 2))
    assert not np.allclose(a, b)
    np.testing.assert_allclose(np.sort(a), np.sort(b))
