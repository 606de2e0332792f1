"""DIFET system tests: partition invariance (the paper's core property),
bundle round-trips, and per-algorithm feature extraction."""
import jax
import numpy as np
import pytest

from repro.configs.difet_paper import DifetConfig, PAPER_ALGORITHMS
from repro.core.bundle import BundleStore, bundle_scenes, tile_scene, rgba_to_gray
from repro.core.engine import extract_features
from repro.data.landsat import synthetic_scene, synthetic_scene_rgba


def counts_for(scene, tile, alg="harris", halo=24):
    cfg = DifetConfig(tile=tile, halo=halo, max_keypoints_per_tile=128)
    b = tile_scene(scene, cfg)
    r = jax.jit(lambda t, h: extract_features(t, h, alg, cfg))(
        b.tiles, b.headers)
    return int(r["total_count"]), r


@pytest.mark.parametrize("alg", ["harris", "fast"])
def test_partition_invariance(alg):
    """Feature counts must not depend on the tiling — the TPU analogue of
    'one mapper per image == many mappers per image' (DESIGN.md §2)."""
    scene = synthetic_scene(200, 300, seed=5)
    c64, _ = counts_for(scene, 64, alg)
    c100, _ = counts_for(scene, 100, alg)
    c256, _ = counts_for(scene, 256, alg)
    assert c64 == c100 == c256, (alg, c64, c100, c256)


def test_counts_positive_per_algorithm():
    scene = synthetic_scene(220, 220, seed=1)
    cfg = DifetConfig(tile=128, halo=24, max_keypoints_per_tile=64)
    b = tile_scene(scene, cfg)
    for alg in PAPER_ALGORITHMS:
        r = jax.jit(lambda t, h, a=alg: extract_features(t, h, a, cfg))(
            b.tiles, b.headers)
        assert int(r["total_count"]) > 0, alg
        assert bool(np.isfinite(np.asarray(r["top_scores"])).all()), alg


def test_keypoint_coordinates_in_bounds():
    scene = synthetic_scene(150, 260, seed=2)
    _, r = counts_for(scene, 100, "harris")
    ys = np.asarray(r["top_ys"])[np.asarray(r["top_valid"])]
    xs = np.asarray(r["top_xs"])[np.asarray(r["top_valid"])]
    assert ys.min() >= 0 and ys.max() < 150
    assert xs.min() >= 0 and xs.max() < 260


def test_descriptor_shapes_and_norms():
    scene = synthetic_scene(200, 200, seed=3)
    cfg = DifetConfig(tile=128, halo=24, max_keypoints_per_tile=32)
    b = tile_scene(scene, cfg)
    r = jax.jit(lambda t, h: extract_features(t, h, "sift", cfg))(
        b.tiles, b.headers)
    desc = np.asarray(r["top_desc"])
    valid = np.asarray(r["top_valid"])
    assert desc.shape[-1] == 128
    if valid.any():
        norms = np.linalg.norm(desc[valid], axis=-1)
        assert np.all(norms < 1.5)
        assert np.all(norms > 0.1)
    r2 = jax.jit(lambda t, h: extract_features(t, h, "orb", cfg))(
        b.tiles, b.headers)
    assert np.asarray(r2["top_desc"]).dtype == np.uint32
    assert np.asarray(r2["top_desc"]).shape[-1] == 8   # 256 bits


def test_descriptor_histogram_adds_in_raster_order():
    """SIFT histograms accumulate each patch's pixels in raster order, as
    sequential float32 adds — the same on every backend and batch size."""
    from repro.core.descriptors import _histogram
    rng = np.random.RandomState(0)
    bins = rng.randint(0, 36, (3, 16, 16)).astype(np.int32)
    w = rng.rand(3, 16, 16).astype(np.float32)
    want = np.zeros((3, 36), np.float32)
    for k in range(3):
        for b, x in zip(bins[k].ravel(), w[k].ravel()):
            want[k, b] = np.float32(want[k, b] + x)
    got = jax.jit(_histogram, static_argnums=2)(bins, w, 36)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_integral_image_is_batch_invariant():
    from repro.core.pyramid import integral_image
    img = np.random.RandomState(1).rand(4, 70, 90).astype(np.float32)
    ii = np.asarray(jax.jit(integral_image)(img))
    assert ii.shape == (4, 71, 91) and not ii[:, 0].any() and not ii[:, :, 0].any()
    np.testing.assert_allclose(ii[:, 1:, 1:],
                               img.astype(np.float64).cumsum(1).cumsum(2),
                               rtol=1e-6)
    one = np.asarray(jax.jit(integral_image)(img[2:3]))
    np.testing.assert_array_equal(one, ii[2:3])


def test_extract_features_fused_equals_seed():
    """The fused SIFT path and the shared FAST response must not change
    extraction results: compare `sift` against the level-by-level seed
    response and the multi-algorithm path against per-algorithm
    extraction, field by field."""
    from repro.core import detectors as D
    from repro.core import engine

    scene = synthetic_scene(220, 220, seed=4)
    cfg = DifetConfig(tile=128, halo=24, max_keypoints_per_tile=64)
    b = tile_scene(scene, cfg)

    def assert_same(ra, rb, tag):
        assert set(ra) == set(rb), tag
        for key in ra:
            a, b = np.asarray(ra[key]), np.asarray(rb[key])
            if a.dtype.kind == "f":
                # float scores/descriptors may differ by ~2 ulp between the
                # two formulations (XLA FMA contraction is shape-dependent)
                np.testing.assert_allclose(a, b, rtol=3e-7, atol=3e-7,
                                           err_msg=f"{tag}/{key}")
            else:
                # counts, positions, validity, packed bits: exact
                np.testing.assert_array_equal(a, b, err_msg=f"{tag}/{key}")

    # --- sift: fused octave path vs seed level-by-level response ----------
    def _sift_resp_seed(img, c, use_pallas):
        return D.sift_dog_response_levelwise(
            img, c.n_octaves, c.scales_per_octave,
            c.sift_contrast_threshold / c.scales_per_octave,
            use_pallas=use_pallas)[0]

    r_fused = extract_features(b.tiles, b.headers, "sift", cfg)
    seed_spec = engine.ALGORITHMS["sift"]._replace(response=_sift_resp_seed)
    orig = engine.ALGORITHMS["sift"]
    try:
        engine.ALGORITHMS["sift"] = seed_spec
        r_seed = extract_features(b.tiles, b.headers, "sift", cfg)
    finally:
        engine.ALGORITHMS["sift"] = orig
    assert_same(r_fused, r_seed, "sift")

    # --- multi-path (shared FAST response) == per-algorithm extraction ----
    from repro.core.engine import extract_features_multi
    algs = ("sift", "fast", "brief", "orb")
    multi = jax.jit(lambda t, h: extract_features_multi(t, h, algs, cfg))(
        b.tiles, b.headers)
    for alg in algs:
        single = jax.jit(lambda t, h, a=alg: extract_features(t, h, a, cfg))(
            b.tiles, b.headers)
        assert_same(multi[alg], single, alg)


def _patches_numpy(img, ys, xs, size):
    """Each keypoint's patch by plain slicing, its start clipped into the
    tile: the reference for ``descriptors.extract_patches``."""
    h, w = img.shape
    out = np.empty((len(ys), size, size), img.dtype)
    for i, (y, x) in enumerate(zip(ys, xs)):
        y0 = min(max(y - size // 2, 0), h - size)
        x0 = min(max(x - size // 2, 0), w - size)
        out[i] = img[y0:y0 + size, x0:x0 + size]
    return out


@pytest.mark.parametrize("n_keys", [512, 128])
@pytest.mark.parametrize("size", [18, 22, 31, 45])
def test_extract_patches_equals_numpy_slicing(size, n_keys):
    """SIFT's 18, SURF's 22, BRIEF's 31 and ORB's 45 px patches at the job's
    512 and the service's 128 keypoints, a batch of tiles under jit and
    vmap as the engine calls it: bit-identical to plain slicing, with
    keypoints on every border and corner so the start clip engages.  The
    backend's path (XLA's window gather here) and the TPU's Pallas kernel,
    interpreted, both."""
    import jax.numpy as jnp
    from repro.core.descriptors import extract_patches
    from repro.kernels import ops
    b, h, w = 3, 70, 300     # neither side a whole number of (8, 128) tiles
    rng = np.random.RandomState(size * 1000 + n_keys)
    tiles = rng.rand(b, h, w).astype(np.float32)
    ys = rng.randint(0, h, (b, n_keys)).astype(np.int32)
    xs = rng.randint(0, w, (b, n_keys)).astype(np.int32)
    edge = [(y, x) for y in (0, 1, h // 2, h - 2, h - 1)
            for x in (0, 1, w // 2, w - 2, w - 1)]
    ys[:, :len(edge)] = [y for y, _ in edge]
    xs[:, :len(edge)] = [x for _, x in edge]
    want = np.stack([_patches_numpy(tiles[i], ys[i], xs[i], size)
                     for i in range(b)])
    got = jax.jit(jax.vmap(
        lambda t, y, x: extract_patches(t, y, x, size)))(tiles, ys, xs)
    np.testing.assert_array_equal(np.asarray(got), want)

    def kernel(t, y, x):
        half = size // 2
        return ops.patches(t, jnp.clip(y - half, 0, h - size),
                           jnp.clip(x - half, 0, w - size), size=size,
                           interpret=True)
    got = jax.jit(jax.vmap(kernel))(tiles, ys, xs)
    np.testing.assert_array_equal(np.asarray(got), want)


PLACEMENTS = {
    "top_row": lambda rng, h, w, k: (np.zeros(k), rng.randint(0, w, k)),
    "left_column": lambda rng, h, w, k: (rng.randint(0, h, k), np.zeros(k)),
    "bottom_right": lambda rng, h, w, k: (rng.randint(h - 24, h, k),
                                          rng.randint(w - 24, w, k)),
    "one_pixel": lambda rng, h, w, k: (np.full(k, rng.randint(h)),
                                       np.full(k, rng.randint(w))),
}


@pytest.mark.parametrize("placement", list(PLACEMENTS))
@pytest.mark.parametrize("size", [18, 22, 31, 45])
def test_extract_patches_at_borders(size, placement):
    """Every keypoint on the tile's top row, its left column or in its
    bottom-right corner, or all K of them on one pixel: the start clip
    engages on one axis, both or none.  Patches of a reflect-padded tile
    (a scene corner, cut as the job cuts it) equal plain numpy slicing,
    from XLA's window gather and from the Pallas kernel, interpreted."""
    import jax.numpy as jnp
    from repro.core.descriptors import extract_patches
    from repro.kernels import ops
    cfg = DifetConfig(tile=128, halo=24, max_keypoints_per_tile=128)
    bundle = tile_scene(synthetic_scene(150, 200, seed=size), cfg)
    tiles = bundle.tiles[-2:]          # the scene's bottom row, padded
    b, h, w = tiles.shape
    k = cfg.max_keypoints_per_tile
    rng = np.random.RandomState(size)
    yx = [PLACEMENTS[placement](rng, h, w, k) for _ in range(b)]
    ys = np.stack([y for y, _ in yx]).astype(np.int32)
    xs = np.stack([x for _, x in yx]).astype(np.int32)
    want = np.stack([_patches_numpy(tiles[i], ys[i], xs[i], size)
                     for i in range(b)])
    got = jax.jit(jax.vmap(
        lambda t, y, x: extract_patches(t, y, x, size)))(tiles, ys, xs)
    np.testing.assert_array_equal(np.asarray(got), want)

    def kernel(t, y, x):
        half = size // 2
        return ops.patches(t, jnp.clip(y - half, 0, h - size),
                           jnp.clip(x - half, 0, w - size), size=size,
                           interpret=True)
    got = jax.jit(jax.vmap(kernel))(tiles, ys, xs)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_extract_patches_is_one_window_a_keypoint():
    """At the job's shape (64 tiles, 512 keypoints, ORB's 45 px) the patches
    must come from a gather of whole windows off a TPU (on one, from the
    kernel: tests/test_chip_compile.py).  An element gather (slice
    sizes all 1, one index a pixel) gives the same values but ran at ~23 ns
    a value on a TPU v5e: 10.4 of the 18.8 s the device was busy in a 20 s
    window of the paper's Landsat-8 job."""
    import re
    import jax.numpy as jnp
    from repro.core.descriptors import extract_patches
    text = jax.jit(jax.vmap(lambda t, y, x: extract_patches(t, y, x, 45))).lower(
        jax.ShapeDtypeStruct((64, 560, 560), jnp.float32),
        jax.ShapeDtypeStruct((64, 512), jnp.int32),
        jax.ShapeDtypeStruct((64, 512), jnp.int32)).as_text()
    sizes = [tuple(int(v) for v in m.split(","))
             for m in re.findall(r"slice_sizes = array<i64: ([\d, ]+)>", text)]
    assert sizes, "extract_patches lowered to no gather"
    assert all(set(s) != {1} for s in sizes), (
        f"an element gather (slice sizes {sizes}) builds the patches: it ran "
        "at ~23 ns a value on a TPU v5e; gather one window a keypoint")


def test_rgba_conversion_and_bundle_roundtrip(tmp_path):
    rgba = synthetic_scene_rgba(120, 140, seed=0)
    gray = rgba_to_gray(rgba)
    assert gray.shape == (120, 140) and gray.dtype == np.float32
    assert 0.0 <= gray.min() and gray.max() <= 1.0
    cfg = DifetConfig(tile=64, halo=16)
    bundle = bundle_scenes([rgba], cfg)
    store = BundleStore(tmp_path)
    store.put("b0", bundle)
    back = store.get("b0")
    np.testing.assert_array_equal(back.tiles, bundle.tiles)
    np.testing.assert_array_equal(back.headers, bundle.headers)
    assert back.cfg.tile == 64


def test_bundle_store_atomic_writes(tmp_path, monkeypatch):
    """A writer crashing mid-write must never surface a truncated npz:
    leftovers are invisible to list()/has_result, and an interrupted
    overwrite leaves the previous committed file intact."""
    import repro.core.bundle as bundle_mod
    cfg = DifetConfig(tile=64, halo=16)
    b0 = tile_scene(synthetic_scene(100, 100, 0), cfg)
    store = BundleStore(tmp_path)
    store.put("b0", b0)
    store.put_result("b0.harris", {"total_count": np.int64(7)})

    # crash leftovers (what a killed writer leaves behind)
    (tmp_path / "junk.npz.tmp").write_bytes(b"\x00" * 64)
    (tmp_path / "junk.result.npz.tmp").write_bytes(b"PK\x03\x04trunc")
    assert store.list() == ["b0"]
    assert not store.has_result("junk")

    # interrupt an overwrite mid-write: the committed b0 must survive
    real_savez = np.savez_compressed

    def dying_savez(f, **arrays):
        real_savez(f, **{k: v[:1] for k, v in arrays.items() if k == "tiles"})
        raise IOError("disk full")

    b1 = tile_scene(synthetic_scene(100, 100, 1), cfg)
    monkeypatch.setattr(bundle_mod.np, "savez_compressed", dying_savez)
    with pytest.raises(IOError):
        store.put("b0", b1)
    monkeypatch.setattr(bundle_mod.np, "savez_compressed", real_savez)
    back = store.get("b0")
    np.testing.assert_array_equal(back.tiles, b0.tiles)   # old data intact
    assert int(store.get_result("b0.harris")["total_count"]) == 7


def test_multi_algorithm_job_matches_single(tmp_path):
    """DifetJob('fast,brief,orb') — the shared-response multi path — must
    store per-algorithm results identical to three single-algorithm jobs."""
    from repro.core.job import DifetJob
    cfg = DifetConfig(tile=64, halo=16, max_keypoints_per_tile=32)
    store = BundleStore(tmp_path / "multi")
    store.put("b0", bundle_scenes([synthetic_scene(100, 120, 3)], cfg))
    multi = DifetJob(store, "fast,brief,orb").run()
    assert multi["bundles_done"] == 1
    assert set(multi["per_algorithm"]) == {"fast", "brief", "orb"}
    for alg in ("fast", "brief", "orb"):
        ref_store = BundleStore(tmp_path / alg)
        ref_store.put("b0", bundle_scenes([synthetic_scene(100, 120, 3)],
                                          cfg))
        single = DifetJob(ref_store, alg).run()
        assert multi["per_algorithm"][alg]["grand_total"] \
            == single["grand_total"]
        rm = store.get_result(f"b0.{alg}")
        rs = ref_store.get_result(f"b0.{alg}")
        assert set(rm) == set(rs)
        for key in rm:
            np.testing.assert_array_equal(rm[key], rs[key], err_msg=key)


def test_pad_to_multiple():
    cfg = DifetConfig(tile=64, halo=16)
    b = tile_scene(synthetic_scene(100, 100, 0), cfg)
    n0 = len(b)
    b2 = b.pad_to(n0 + 3)
    assert len(b2) == n0 + 3
    assert (b2.headers[n0:, 5] == 1).all()   # pad flag set
    r = jax.jit(lambda t, h: extract_features(t, h, "harris", b2.cfg))(
        b2.tiles, b2.headers)
    r0 = jax.jit(lambda t, h: extract_features(t, h, "harris", b.cfg))(
        b.tiles, b.headers)
    assert int(r["total_count"]) == int(r0["total_count"])   # pads emit nothing



RESPONSE_SCOPE = {"harris": "harris", "shi_tomasi": "shi_tomasi",
                  "sift": "sift", "surf": "surf", "fast": "fast",
                  "brief": "fast", "orb": "fast"}


@pytest.mark.parametrize("alg", sorted(RESPONSE_SCOPE))
def test_named_scopes_in_job_and_serve_programs(alg, tmp_path):
    """The job's sharded program and the serve step carry the named
    scopes a device trace is read by: the response function's
    ``difet.response.<fn>``, and ``difet.<alg>`` over ``nms``, ``topk``,
    ``describe`` (descriptor algorithms) and ``reduce``."""
    import re

    from repro.core.engine import ALGORITHMS, make_serve_step
    from repro.core.job import DifetJob
    from repro.distributed.sharding import data_mesh
    cfg = DifetConfig(tile=32, halo=8, max_keypoints_per_tile=16)
    tiles = np.zeros((2, 48, 48), np.float32)
    headers = np.zeros((2, 6), np.int32)
    job = DifetJob(BundleStore(tmp_path), alg, mesh=data_mesh(1),
                   manifest_path=tmp_path / "job.manifest.json")
    stages = ["nms", "topk", "reduce"]
    if ALGORITHMS[alg].describe is not None:
        stages.append("describe")
    for fn in (job._sharded_fn(tiles.shape, cfg),
               make_serve_step((alg,), cfg)):
        text = fn.lower(tiles, headers).as_text(debug_info=True)
        assert f"difet.response.{RESPONSE_SCOPE[alg]}" in text
        for stage in stages:
            assert re.search(rf"difet\.{alg}\)?/{stage}\b", text), stage
        if "describe" not in stages:
            assert not re.search(rf"difet\.{alg}\)?/describe", text)
