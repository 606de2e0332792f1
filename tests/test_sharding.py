"""Sharding of the tile path: the data mesh and the batch spec."""
import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import batch_pspec, data_mesh, dp_axes


def test_dp_axes(mesh_16x16, mesh_pod):
    assert dp_axes(mesh_16x16) == ("data",)
    assert dp_axes(mesh_pod) == ("pod", "data")


def test_data_mesh_one_device():
    mesh = data_mesh(1)
    assert mesh.axis_names == ("data",)
    assert mesh.devices.shape == (1,)
    assert list(mesh.devices.flat) == jax.devices()[:1]


def test_data_mesh_defaults_to_every_device():
    mesh = data_mesh(None)
    assert mesh.axis_names == ("data",)
    assert list(mesh.devices.flat) == jax.devices()


@pytest.mark.parametrize("n", [0, len(jax.devices()) + 1],
                         ids=["zero", "above_device_count"])
def test_data_mesh_refuses_out_of_range(n):
    with pytest.raises(ValueError, match="outside"):
        data_mesh(n)


@pytest.mark.parametrize("rank", [2, 3, 4])
def test_batch_pspec_splits_only_the_batch(rank, mesh_16x16):
    """Tiles [N, H, W], headers [N, 6]: only N is split, over ``data``."""
    assert batch_pspec(mesh_16x16, rank) == P("data", *([None] * (rank - 1)))


def test_batch_pspec_on_pod_mesh(mesh_pod):
    """A multi-pod mesh splits the batch over both data axes, pod first,
    and never over ``model``."""
    assert batch_pspec(mesh_pod, 3) == P(("pod", "data"), None, None)
