"""Host mesh construction.

``make_host_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — device count is
locked on first jax init, and smoke tests must keep seeing 1 CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_host_mesh():
    """Degenerate mesh over however many devices the host actually has —
    used by smoke tests and the CPU examples."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))
