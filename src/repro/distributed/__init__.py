from repro.distributed.sharding import (  # noqa: F401
    batch_pspec, dp_axes, data_mesh,
)
