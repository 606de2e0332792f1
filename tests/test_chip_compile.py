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
