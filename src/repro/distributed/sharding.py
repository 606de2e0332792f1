"""Sharding for the DIFET tile path: a 1-D ``data`` mesh over the chips,
and the one spec the path needs — the tile batch split over the data axes,
everything per tile kept local."""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def dp_axes(mesh: Mesh):
    """Data-parallel mesh axes (pod-major on multi-pod meshes)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def batch_pspec(mesh: Mesh, rank: int = 2) -> P:
    """PartitionSpec sharding only the leading (batch) dim over the data
    axes: ``P(data, None, ...)`` padded to ``rank``.  This is the one spec
    the DIFET tile path needs — tiles ``[N, H, W]`` and headers ``[N, 6]``
    both split over ``N``, everything per-tile stays local (the paper's
    "good locality": the map needs no cross-tile communication)."""
    dp = dp_axes(mesh)
    dp = dp[0] if len(dp) == 1 else dp
    return P(dp, *([None] * (rank - 1)))


def data_mesh(n_devices: Optional[int] = None) -> Mesh:
    """A 1-D ``("data",)`` mesh over the first ``n_devices`` local devices
    (all of them by default) — the mesh shape of the DIFET extraction
    workload, where the only parallel axis is the tile batch.  On a
    single-device host this degrades to a size-1 mesh, under which every
    sharding constraint is a no-op but the same code paths compile."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if not 1 <= n <= len(devs):
        raise ValueError(f"n_devices={n} outside [1, {len(devs)}]")
    return Mesh(np.asarray(devs[:n]), ("data",))
