"""DIFET feature-extraction serving subsystem (DESIGN.md §8).

``FeatureService`` is the facade: request/response model in ``api.py``,
continuous-batching scheduler in ``scheduler.py``, shape buckets + the
per-(bucket, algorithm-set) compile cache in ``buckets.py``, and the
content-hash result caches (in-process LRU + shared disk tier) in
``cache.py``.  The fleet layer replicates the service: consistent-hash
router with admission control in ``router.py``, replica pool + lifecycle
+ SLO-driven autoscaling in ``fleet.py``, and the shared synthetic
trace generator in ``trace.py``.  Cross-process replicas live in
``proc.py`` (worker + parent-side client) over the spooled-file
transport in ``transport.py``; deterministic fault injection for both
tests and launch drivers in ``chaos.py``.
"""
from repro.serve.api import (FeatureService, ServeConfig, ExtractResponse,  # noqa: F401
                             ResponseHandle, ServiceOverloaded, tile_digest,
                             config_digest, encode_tile, decode_tile)
from repro.serve.buckets import BucketTable, CompileCache, warmup  # noqa: F401
from repro.serve.cache import (ResultCache, DiskCacheTier,  # noqa: F401
                               TieredResultCache)
from repro.serve.chaos import ChaosPlan, cache_partition, sigkill, tear_file  # noqa: F401
from repro.serve.fleet import Fleet, FleetConfig  # noqa: F401
from repro.serve.proc import ProcReplicaClient, ProcHandle  # noqa: F401
from repro.serve.router import (Router, RouterConfig, Shed, FleetHandle,  # noqa: F401
                                HashRing, TokenBucket)
from repro.serve.scheduler import (BatchScheduler, WorkItem, ServiceClosed,  # noqa: F401
                                   ReplicaDied)
from repro.serve.trace import (TraceConfig, TraceEvent, make_trace,  # noqa: F401
                               tile_pool, scene_key)
from repro.serve.transport import WorkerMailbox  # noqa: F401
