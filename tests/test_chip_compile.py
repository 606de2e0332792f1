"""Compile the main path's Pallas kernels for a TPU v5e, without a chip.

The TPU compiler ships with libtpu and compiles for a described
``v5e:2x2`` topology: it refuses what the chip would refuse (scoped-VMEM
overflows, block shapes the Mosaic lowering rejects) where interpret
mode runs anything.  Each test compiles one kernel at the paper's tile —
512 px + 2·24 px halo = 560 px, batch 8 — and asserts that a Mosaic
kernel (``tpu_custom_call``) is in the compiled program.  Nothing runs.

The topology is described once per module, inside a fixture: only one
process at a time may load the TPU library, so describing it at import
would make the test workers collect different tests.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.configs.difet_paper import DifetConfig
from repro.kernels import ops

TILE = DifetConfig().tile + 2 * DifetConfig().halo      # 560
BATCH = 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def compile_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def tiles(sharding, hw=TILE, n=BATCH):
    return jax.ShapeDtypeStruct((n, hw, hw), jnp.float32, sharding=sharding)


@pytest.mark.parametrize("kernel", [
    functools.partial(ops.harris, interpret=False),
    functools.partial(ops.harris, shi_tomasi=True, interpret=False),
    functools.partial(ops.gaussian_blur, sigma=1.6, interpret=False),
    functools.partial(ops.fast_score, interpret=False),
], ids=["harris", "shi_tomasi", "gaussian_blur", "fast_score"])
def test_detector_kernel_compiles_at_paper_tile(one_chip, kernel):
    assert "tpu_custom_call" in compile_text(kernel, tiles(one_chip))


def test_scalespace_octave_compiles_at_gated_tile(one_chip):
    """304 px (tile 256 + halo) is inside the fused octave's VMEM gate."""
    assert ops.scalespace_fits_vmem(304, 304, 3)
    fn = functools.partial(ops.scalespace_octave, scales_per_octave=3,
                           contrast_threshold=0.01, interpret=False)
    assert "tpu_custom_call" in compile_text(fn, tiles(one_chip, 304))


@pytest.mark.parametrize("size", [45, 18])
def test_patch_kernel_compiles_at_job_shape(one_chip, monkeypatch, size):
    """On a TPU the descriptors' patches come from the Pallas kernel, not
    XLA's gather (which there serves one index or one slice at a time):
    the job's 64 tiles a bundle and 512 keypoints, ORB's 45 and SIFT's 18
    px patches."""
    from repro.core.descriptors import extract_patches
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    keys = jax.ShapeDtypeStruct((64, 512), jnp.int32, sharding=one_chip)
    text = compile_text(jax.vmap(functools.partial(extract_patches,
                                                   size=size)),
                        tiles(one_chip, n=64), keys, keys)
    assert "tpu_custom_call" in text
    assert " gather(" not in text


def test_orb_compiles_without_a_gather_at_job_shape(one_chip, monkeypatch):
    """ORB at the job's shape on a TPU: the patches from the Pallas kernel
    and the rotated pairs from a one-hot contraction, so no gather is
    left in the program."""
    from repro.core.descriptors import orb_descriptors
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    keys = jax.ShapeDtypeStruct((64, 512), jnp.int32, sharding=one_chip)
    text = compile_text(jax.vmap(orb_descriptors), tiles(one_chip, n=64),
                        keys, keys)
    assert "tpu_custom_call" in text
    assert " gather(" not in text


@pytest.fixture(scope="module")
def attached_tpu():
    if jax.default_backend() != "tpu":
        pytest.skip("runs on a TPU only (JAX_PLATFORMS=tpu on a TPU host)")


def test_orb_pair_selection_is_exact_on_the_chip(attached_tpu, monkeypatch):
    """On a TPU the one-hot contraction runs on the MXU at HIGHEST
    precision, which splits each f32 value into three bf16 parts; against
    a 1.0 they sum back to the value exactly.  At the job's shape (64
    tiles of 560 px, 512 keypoints, on every border and corner too): the
    selected values of full-mantissa patches of mixed sign and scale, and
    the descriptors, bit for bit the element gather's."""
    from repro.core import descriptors as D
    from repro.data.landsat import synthetic_scene
    rng = np.random.RandomState(0)
    p = rng.randn(64 * 512, 45, 45) * 10.0 ** rng.uniform(-6, 6, (64 * 512, 45, 45))
    p = p.astype(np.float32)
    ry = rng.randint(0, 45, (64 * 512, 256)).astype(np.int32)
    rx = rng.randint(0, 45, (64 * 512, 256)).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(D._select_points)(p, ry, rx)),
        np.asarray(jax.jit(_take_points)(p, ry, rx)))

    n, k = 64, 512
    scene = synthetic_scene(8 * TILE, 8 * TILE, seed=1)
    tl = scene.reshape(8, TILE, 8, TILE).transpose(0, 2, 1, 3).reshape(
        n, TILE, TILE)
    ys = rng.randint(0, TILE, (n, k)).astype(np.int32)
    xs = rng.randint(0, TILE, (n, k)).astype(np.int32)
    edge = [(y, x) for y in (0, 1, TILE // 2, TILE - 2, TILE - 1)
            for x in (0, 1, TILE // 2, TILE - 2, TILE - 1)]
    ys[:, :len(edge)] = [y for y, _ in edge]
    xs[:, :len(edge)] = [x for _, x in edge]
    got = np.asarray(jax.jit(jax.vmap(D.orb_descriptors))(tl, ys, xs))
    monkeypatch.setattr(D, "_select_points", _take_points)
    want = np.asarray(jax.jit(jax.vmap(D.orb_descriptors))(tl, ys, xs))
    np.testing.assert_array_equal(got, want)


def _take_points(patches, ys, xs):
    """ORB's pair lookup as an element gather: the one-hot selection's
    oracle."""
    p = patches.shape[-1]
    return jnp.take_along_axis(patches.reshape(patches.shape[0], -1),
                               ys * p + xs, axis=1)


@pytest.mark.parametrize("metric,path", [
    ("l2", "pallas_resident"), ("l2", "pallas_stream"),
    ("hamming", "pallas_resident"), ("hamming", "pallas_stream")])
def test_matcher_kernel_compiles_with_many_query_blocks(one_chip, metric,
                                                        path):
    nq, nk = 512, 4096                 # 4 query blocks
    d, dt = (128, jnp.float32) if metric == "l2" else (8, jnp.uint32)
    fn = functools.partial(ops._match_impl, metric=metric, path=path,
                           interpret=False)
    text = compile_text(
        fn, jax.ShapeDtypeStruct((nq, d), dt, sharding=one_chip),
        jax.ShapeDtypeStruct((nk, d), dt, sharding=one_chip),
        jax.ShapeDtypeStruct((nk,), jnp.bool_, sharding=one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("use_pallas", [False, True])
def test_difet_job_sharded_program_compiles_on_four_chips(topo, tmp_path,
                                                          monkeypatch,
                                                          use_pallas):
    from repro.core.bundle import BundleStore
    from repro.core.job import DifetJob
    from repro.distributed.sharding import batch_pspec
    # the kernels pick interpret mode from the default backend, which is
    # this host's CPU: steer them to the compiled kernels the chip takes
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    cfg = DifetConfig()
    job = DifetJob(BundleStore(tmp_path), "harris", mesh=mesh,
                   use_pallas=use_pallas)
    fn = job._sharded_fn((16, TILE, TILE), cfg)
    compiled = fn.lower(
        tiles(NamedSharding(mesh, batch_pspec(mesh, 3)), n=16),
        jax.ShapeDtypeStruct((16, 6), jnp.int32,
                             sharding=NamedSharding(mesh,
                                                    batch_pspec(mesh, 2)))
    ).compile()
    assert len(compiled.input_shardings[0][0].device_set) == 4
    assert ("tpu_custom_call" in compiled.as_text()) == use_pallas
