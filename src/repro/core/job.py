"""Checkpointed, restartable jobs: the Hadoop JobTracker's roles map to:
  * task re-execution on failure  → a JSON manifest with a processed-item
    bitmap; on restart, only missing items are (deterministically)
    re-executed — results are bit-identical, so re-execution is safe.
  * speculative execution for stragglers → over-decomposition: each bundle
    is split into ``shards_per_bundle`` independent shards; a shard that
    dies mid-flight only forfeits its own tiles.  On membership change
    (elastic scaling) the outstanding work queue is re-balanced across the
    new worker set — no global restart.

``ManifestJob`` is the generic machinery (manifest + atomic commit + resume
loop + per-worker leases); ``DifetJob`` is the extraction phase over
bundles, and the stitching workload's pairwise-registration phase
(`core/mosaic.py::MatchPhase`) reuses the same machinery for its match
manifest.

Read-ahead: a subclass that defines ``load`` splits each item into a
host-side load stage and ``process``.  ``run`` then loads item *n+1* on
one background thread (`_ReadAhead`) while item *n* is processed and
committed, so the store read leaves the critical path; items are still
loaded, processed and committed in manifest order.

Multi-worker protocol (docs/scaling.md): the manifest's item order is
fixed at creation and never rewritten — restart-determinism means any
worker count walks the *same* ordered list.  Workers coordinate through
``LeaseBoard``: an item is claimed by atomically creating a sidecar lease
file; a crashed worker's lease expires after ``ttl_s`` and any live
worker re-claims the item.  Because processing is deterministic and the
result commit is atomic, a lease race at worst duplicates work — it never
corrupts a result.  That is what makes the worker count *elastic*: kill
workers, restart with more or fewer, and the job resumes cleanly.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.bundle import BundleStore, TileBundle
from repro.core.engine import (extract_features, extract_features_multi,
                               map_tiles_multi, reduce_features_multi)
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


@dataclasses.dataclass
class JobManifest:
    """The on-disk job state: ordered work items + their done bitmap.

    ``bundle_names`` is fixed at creation and NEVER rewritten — the
    restart-determinism contract: every restart, and every worker of an
    elastic pool, walks the same ordered list (leases partition it).

    Fields:
        algorithm:         job name (extraction jobs: the algorithm string).
        bundle_names:      work-item names in execution order.
        done:              item name -> committed flag.
        started_at:        epoch seconds at manifest creation.
        shards_per_bundle: over-decomposition factor (straggler bound).
    """
    algorithm: str
    bundle_names: List[str]
    done: Dict[str, bool]
    started_at: float
    shards_per_bundle: int = 4

    def to_json(self) -> str:
        """Serialize for the atomic manifest commit."""
        return json.dumps(dataclasses.asdict(self), indent=1)

    @classmethod
    def from_json(cls, s: str) -> "JobManifest":
        """Parse a manifest previously written by `to_json`."""
        return cls(**json.loads(s))

    @property
    def remaining(self) -> List[str]:
        """Unprocessed item names, in manifest (execution) order."""
        return [b for b in self.bundle_names if not self.done.get(b)]


class LeaseBoard:
    """Per-item worker leases: filesystem claims for elastic worker pools.

    ``acquire(item, worker)`` claims an item by creating
    ``<item>.lease`` with ``O_CREAT | O_EXCL`` — the same cross-process
    atomicity the manifest commit relies on.  A lease older than
    ``ttl_s`` is considered orphaned (its worker died) and is stolen with
    an atomic replace.  Re-acquiring one's own lease refreshes it.

    The board is an *optimization*, not a correctness boundary: item
    processing is deterministic and result commits are atomic, so the
    worst outcome of a steal race is two workers redundantly computing
    the same bit-identical result (MapReduce speculative execution).
    """

    def __init__(self, root, ttl_s: float = 600.0):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.ttl_s = ttl_s

    def _path(self, item: str) -> Path:
        return self.root / f"{item}.lease"

    def _write(self, path: Path, worker: str) -> None:
        # unique tmp per writer (two stealers racing must not consume each
        # other's tmp file; the losing replace just overwrites benignly)
        tmp = path.with_suffix(
            f".tmp.{os.getpid()}.{threading.get_ident()}")
        tmp.write_text(json.dumps({"worker": worker, "t": time.time()}))
        tmp.replace(path)

    def acquire(self, item: str, worker: str) -> bool:
        """Try to claim ``item`` for ``worker``; True on success (including
        refreshing a lease this worker already holds or stealing a stale
        one)."""
        path = self._path(item)
        try:
            fd = os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
        except FileExistsError:
            try:
                lease = json.loads(path.read_text())
            except (OSError, ValueError):
                lease = None                    # mid-write/corrupt: steal
            if lease is not None:
                if lease.get("worker") == worker:
                    self._write(path, worker)   # refresh our own lease
                    return True
                if time.time() - lease.get("t", 0.0) < self.ttl_s:
                    return False                # live lease held elsewhere
            self._write(path, worker)           # stale/orphaned: steal
            obs_metrics.registry().counter("difet.job.lease_steals").inc()
            return True
        with os.fdopen(fd, "w") as f:
            json.dump({"worker": worker, "t": time.time()}, f)
        obs_metrics.registry().counter("difet.job.lease_acquires").inc()
        return True

    def release(self, item: str, worker: str) -> None:
        """Drop ``worker``'s lease on ``item`` (no-op if not held)."""
        path = self._path(item)
        try:
            if json.loads(path.read_text()).get("worker") == worker:
                path.unlink()
        except (OSError, ValueError):
            pass

    def holder(self, item: str) -> Optional[Tuple[str, float]]:
        """``(worker, age_s)`` of the current lease on ``item``, or None
        if unleased (or the lease file is torn mid-write)."""
        try:
            lease = json.loads(self._path(item).read_text())
            return (lease["worker"], time.time() - lease.get("t", 0.0))
        except (OSError, ValueError, KeyError):
            return None

    def fresh(self, item: str) -> bool:
        """Is ``item`` held by a lease younger than ``ttl_s``?  The
        liveness predicate fleets use: a worker that stops heartbeating
        (re-acquiring its own lease) goes stale after one TTL."""
        h = self.holder(item)
        return h is not None and h[1] < self.ttl_s


class _ReadAhead:
    """Depth-1 read-ahead over a job's claimed items: while the caller
    processes one item, one background thread claims the next and runs
    ``load`` on it.  Iterating yields ``(name, loaded)`` in claim order,
    each after the caller's wait for it, recorded as a ``wait_load`` span
    (layer ``job``; flight recorder only, so the job's profiler spans stay
    those of the sequential loop, and a profiler trace reads the wait as
    the gap between ``bundle`` spans); a ``load`` that raises raises
    there, at its item.  ``close``
    stops and joins the thread and returns the name of an item it read
    ahead that was never taken (None if none), which is dropped."""

    def __init__(self, load: Callable[[str], object], claims: Iterator[str]):
        self._load, self._claims = load, claims
        self._pool = ThreadPoolExecutor(
            1, thread_name_prefix="difet-job-reader")
        self._ahead = self._pool.submit(self._next)

    def _next(self) -> Optional[Tuple[str, object]]:
        # runs on the reader thread only, so ``claims`` (and in pool mode
        # the lease it takes) advances one item at a time, before the load
        name = next(self._claims, None)
        return None if name is None else (name, self._load(name))

    def __iter__(self) -> Iterator[Tuple[str, object]]:
        first = True
        while True:
            t0 = time.monotonic()
            got = self._ahead.result()
            obs_trace.emit_span("wait_load", "job", t0, time.monotonic())
            if got is None:
                return
            self._ahead = self._pool.submit(self._next)
            if not first:
                obs_metrics.registry().counter(
                    "difet.job.readahead_hits").inc()
            first = False
            yield got

    def close(self) -> Optional[str]:
        self._ahead.cancel()
        self._pool.shutdown(wait=True)
        ahead = self._ahead
        if ahead.cancelled() or ahead.exception() is not None \
                or ahead.result() is None:
            return None
        obs_metrics.registry().counter("difet.job.readahead_dropped").inc()
        return ahead.result()[0]


class ManifestJob:
    """Checkpointed work queue over named items.

    ``run()`` is restartable: it consults the manifest, processes only
    missing items via ``process(name)`` (subclass hook), and commits the
    manifest write-tmp-then-rename after each item — the MapReduce "task
    commit" analogue.  A subclass that also defines ``load(name)`` gets
    read-ahead: ``run`` calls ``process(name, load(name))``, with the
    next item's ``load`` running on a background thread meanwhile.
    ``simulate_failure_after`` kills the job after N items (used by the
    fault-tolerance tests).

    ``run(worker_id=...)`` joins an elastic worker pool: items are walked
    in manifest order but claimed through the job's `LeaseBoard`, so any
    number of concurrent workers (or restarts with a *different* worker
    count) partition the remaining work without a coordinator.
    """

    def __init__(self, store: BundleStore, job_name: str,
                 items: Optional[Sequence[str]] = None, manifest_path=None,
                 shards_per_bundle: int = 4, lease_ttl_s: float = 600.0):
        self.store = store
        self.job_name = job_name
        self.manifest_path = Path(manifest_path or
                                  store.root / f"{job_name}.manifest.json")
        self.shards_per_bundle = shards_per_bundle
        self.lease_ttl_s = lease_ttl_s
        self._items = items
        # the reader thread ORs done marks from disk while the main thread
        # serializes the manifest
        self._done_lock = threading.Lock()
        self.manifest = self._load_or_create()

    def _load_or_create(self) -> JobManifest:
        if self.manifest_path.exists():
            return JobManifest.from_json(self.manifest_path.read_text())
        names = (list(self._items) if self._items is not None
                 else self.store.list())
        m = JobManifest(self.job_name, names, {n: False for n in names},
                        time.time(), self.shards_per_bundle)
        self._commit(m)
        return m

    def _commit(self, manifest: JobManifest) -> None:
        # tmp name is unique per writer: concurrent workers committing the
        # same manifest must not consume each other's tmp file mid-replace
        tmp = self.manifest_path.with_suffix(
            f".tmp.{os.getpid()}.{threading.get_ident()}")
        with self._done_lock:
            text = manifest.to_json()
        tmp.write_text(text)
        tmp.replace(self.manifest_path)      # atomic manifest update
        obs_metrics.registry().counter("difet.job.manifest_commits").inc()

    def _merge_done_from_disk(self) -> None:
        """OR the on-disk manifest's done map into memory (tolerates a
        concurrent writer; a failed read just keeps the local view)."""
        try:
            disk = JobManifest.from_json(self.manifest_path.read_text())
        except (OSError, ValueError, TypeError):
            return
        with self._done_lock:
            for n, d in disk.done.items():
                if d:
                    self.manifest.done[n] = True

    def _commit_merged(self) -> None:
        """Multi-worker commit: re-read the on-disk manifest and OR the
        done maps before the atomic replace, so concurrent workers don't
        erase each other's marks.  The residual read-replace race only
        drops a *mark*, never a result (results live in the store and are
        re-checked), so a re-run self-heals."""
        self._merge_done_from_disk()
        self._commit(self.manifest)

    @property
    def leases(self) -> LeaseBoard:
        """The job's lease board (sidecar dir next to the manifest)."""
        if not hasattr(self, "_leases"):
            self._leases = LeaseBoard(
                self.manifest_path.with_suffix(".leases"),
                ttl_s=self.lease_ttl_s)
        return self._leases

    #: Optional load stage (subclass hook): ``load(name)`` does an item's
    #: host-side reading, and ``process(name, loaded)`` gets its result.
    #: Defined, it turns on read-ahead in ``run``; ``None``, items are
    #: processed one after another with ``process(name)``.
    load: Optional[Callable[[str], object]] = None

    def process(self, name: str, *loaded) -> None:
        """Produce + commit the result for one item (subclass hook);
        ``loaded`` is what ``load(name)`` returned, where it is defined."""
        raise NotImplementedError

    def _claims(self, worker_id: Optional[str]) -> Iterator[str]:
        """The remaining items this run takes, in manifest order; in pool
        mode only those this worker could lease, each leased before it is
        yielded."""
        for name in list(self.manifest.remaining):
            if worker_id is not None:
                if self.manifest.done.get(name):
                    continue
                # a peer may have finished this item after our snapshot:
                # one cheap manifest re-read avoids re-extracting a whole
                # bundle (work, not correctness — results are idempotent)
                self._merge_done_from_disk()
                if self.manifest.done.get(name):
                    continue
                if not self.leases.acquire(name, worker_id):
                    continue                    # leased by a live worker
            yield name

    def run(self, simulate_failure_after: Optional[int] = None,
            progress: Optional[Callable[[str], None]] = None,
            worker_id: Optional[str] = None) -> Dict:
        """Process remaining items in manifest order; returns `summary()`.
        Each item runs in a ``bundle`` span (layer ``job``, `obs/trace.py`)
        around ``process`` and its manifest commit (``commit``).  With a
        ``load`` stage the next item is claimed and loaded ahead on one
        reader thread; however ``run`` leaves, that thread is joined
        before it returns and an item it read ahead unprocessed is
        dropped (its lease released).

        Args:
            simulate_failure_after: raise after N items (fault-tolerance
                tests — the restart path is the recovery protocol).
            progress: optional per-item callback with the item name.
            worker_id: join the elastic worker pool under this identity —
                items are claimed via the lease board, skipped when
                another live worker holds them, and released on commit.
                ``None`` (single-worker mode) bypasses leasing entirely.
        """
        claims = self._claims(worker_id)
        reader = None if self.load is None else _ReadAhead(self.load, claims)
        items = (((name, ()) for name in claims) if reader is None else
                 ((name, (loaded,)) for name, loaded in reader))
        processed = 0
        try:
            for name, loaded in items:
                with obs_trace.span("bundle", "job", item=name):
                    self.process(name, *loaded)
                    self.manifest.done[name] = True
                    with obs_trace.span("commit", "job"):
                        if worker_id is not None:
                            self._commit_merged()
                        else:
                            self._commit(self.manifest)
                if worker_id is not None:
                    self.leases.release(name, worker_id)
                processed += 1
                if progress:
                    progress(name)
                if simulate_failure_after is not None \
                        and processed >= simulate_failure_after:
                    raise RuntimeError(
                        f"simulated worker failure after {name}")
        finally:
            dropped = reader.close() if reader is not None else None
            if dropped is not None and worker_id is not None:
                self.leases.release(dropped, worker_id)
        return self.summary()

    def summary(self) -> Dict:
        """Progress report: ``{job, bundles_done, bundles_total}``."""
        done = [n for n, d in self.manifest.done.items() if d]
        return {"job": self.job_name, "bundles_done": len(done),
                "bundles_total": len(self.manifest.bundle_names)}

    # ---- elastic scaling ----------------------------------------------------
    def rebalance(self, n_workers: int) -> List[List[str]]:
        """Partition outstanding items across a (new) worker count —
        called on membership change; returns per-worker work lists."""
        rem = self.manifest.remaining
        return [rem[i::n_workers] for i in range(n_workers)]


class DifetJob(ManifestJob):
    """Checkpointed distributed extraction over a BundleStore.

    ``algorithm`` may be a single name or a comma-separated list
    (``"fast,brief,orb"``): multi-algorithm extraction routes through
    ``extract_features_multi`` so algorithms sharing a response function
    compute it once per tile; results are stored per algorithm
    (``<bundle>.<alg>``), identical to single-algorithm runs.

    With ``mesh`` set, every shard's tile batch is device-sharded over the
    mesh's data axes (`sharding.batch_pspec`): the batch is pad-flagged up
    to a device-count multiple, extracted under a jit with explicit input
    shardings (one compiled program per batch shape), and the result is
    sliced back — bit-identical to the same jitted program without input
    shardings, since pad tiles are masked before the reduce and
    `lax.top_k` tie-breaks by index (sharding is a layout change, never a
    numerics change; the eager no-mesh path may differ in float ulps from
    any jitted path because XLA fuses differently).
    """

    def __init__(self, store: BundleStore, algorithm: str,
                 manifest_path=None, shards_per_bundle: int = 4,
                 extractor: Optional[Callable] = None, mesh=None,
                 use_pallas: bool = False, lease_ttl_s: float = 600.0):
        # a custom extractor's output is opaque — store it under the full
        # job name rather than splitting into per-algorithm results
        if extractor is not None:
            self.algorithms = (algorithm,)
        else:
            self.algorithms = tuple(a.strip() for a in algorithm.split(",")
                                    if a.strip())
            algorithm = ",".join(self.algorithms)   # normalized whitespace
        self.algorithm = algorithm
        self.extractor = extractor
        self.mesh = mesh
        self.use_pallas = use_pallas
        self._sharded_fns: Dict[tuple, Callable] = {}
        super().__init__(store, algorithm, manifest_path=manifest_path,
                         shards_per_bundle=shards_per_bundle,
                         lease_ttl_s=lease_ttl_s)

    def _shards(self, bundle: TileBundle) -> List[TileBundle]:
        """Over-decomposition for straggler mitigation: split tiles into
        independent shards so slow/failed work is bounded per shard.  The
        split is contiguous, so each shard is a view of the bundle."""
        n = max(1, min(self.shards_per_bundle, len(bundle)))
        return [TileBundle(t, h, bundle.cfg)
                for t, h in zip(np.array_split(bundle.tiles, n),
                                np.array_split(bundle.headers, n))
                if len(t)]

    # ---- mesh-sharded extraction -------------------------------------------
    def _data_size(self) -> int:
        from repro.distributed.sharding import dp_axes
        return int(np.prod([self.mesh.shape[a]
                            for a in dp_axes(self.mesh)] or [1]))

    def _sharded_fn(self, tiles_shape, cfg) -> Callable:
        """One jitted, input-sharded program per (algorithms, batch shape,
        config); cached so a streaming pipeline's fixed-shape batches
        compile exactly once.  The per-tile map runs under ``shard_map``
        (each device maps its own tiles — the compiler cannot partition
        a Pallas kernel by itself), and the reduce runs on the gathered
        per-tile results."""
        import functools
        import jax
        from jax.sharding import NamedSharding
        from repro.distributed.sharding import batch_pspec
        key = (self.algorithms, tuple(tiles_shape), cfg)
        if key not in self._sharded_fns:
            specs = (batch_pspec(self.mesh, 3), batch_pspec(self.mesh, 2))
            per_tile = jax.shard_map(
                functools.partial(map_tiles_multi,
                                  algorithms=self.algorithms, cfg=cfg,
                                  use_pallas=self.use_pallas),
                mesh=self.mesh, in_specs=specs,
                out_specs=batch_pspec(self.mesh, 1),
                check_vma=False)       # pallas_call outputs carry no vma
            self._sharded_fns[key] = jax.jit(
                lambda t, h: reduce_features_multi(per_tile(t, h)),
                in_shardings=tuple(NamedSharding(self.mesh, s)
                                   for s in specs))
        return self._sharded_fns[key]

    @staticmethod
    def _slice_result(res: Dict, n: int, k: int) -> Dict:
        """Undo device-count padding: drop pad rows from per-tile arrays
        and re-truncate the top-K merge to the unpadded candidate count.
        Pad tiles are all-invalid (-inf before top_k, which tie-breaks by
        index), so the kept prefix is bit-identical to the unpadded run."""
        out = dict(res)
        out["per_tile_count"] = res["per_tile_count"][:n]
        kk = min(k * 4, n * k)
        for key in ("top_scores", "top_ys", "top_xs", "top_valid",
                    "top_desc"):
            if key in res:
                out[key] = res[key][:kk]
        return out

    def _extract(self, tiles, headers, cfg) -> Dict[str, Dict]:
        if self.extractor is not None:
            return {self.algorithm: self.extractor(tiles, headers)}
        if self.mesh is not None:
            import jax
            n = tiles.shape[0]
            pad = (-n) % self._data_size()
            b = TileBundle(np.asarray(tiles), np.asarray(headers),
                           cfg).pad_to(n + pad)
            out = self._sharded_fn(b.tiles.shape, cfg)(b.tiles, b.headers)
            # waits for the device, then one host transfer
            with obs_trace.span("fetch", "job"):
                out = jax.device_get(out)
            return {alg: self._slice_result(r, n,
                                            cfg.max_keypoints_per_tile)
                    for alg, r in out.items()}
        if len(self.algorithms) > 1:
            return extract_features_multi(tiles, headers, self.algorithms,
                                          cfg, use_pallas=self.use_pallas)
        return {self.algorithm:
                extract_features(tiles, headers, self.algorithm, cfg,
                                 use_pallas=self.use_pallas)}

    def load(self, name: str) -> List[TileBundle]:
        """Read one bundle and split it into shards: span ``get`` (layer
        ``job``), on the job's reader thread (`ManifestJob.run`)."""
        with obs_trace.span("get", "job"):
            return self._shards(self.store.get(name))

    def process(self, name: str, shards: List[TileBundle]) -> None:
        """Extract one loaded bundle: extract each shard (device-sharded
        when a mesh is set), merge shard partials, and commit one
        ``<name>.<algorithm>`` result per algorithm to the store.  Spans
        (layer ``job``): ``extract`` per shard (on a mesh with its
        ``fetch``, the wait for the device and the transfer), ``merge``
        and ``put`` (the result writes)."""
        partials: Dict[str, List[Dict]] = {}
        for shard in shards:
            with obs_trace.span("extract", "job"):
                r = self._extract(shard.tiles, shard.headers, shard.cfg)
                for alg, res in r.items():
                    partials.setdefault(alg, []).append(
                        {k: np.asarray(v) for k, v in res.items()})
        with obs_trace.span("merge", "job"):
            merged = {alg: self._merge(parts)
                      for alg, parts in partials.items()}
        with obs_trace.span("put", "job"):
            for alg, res in merged.items():
                self.store.put_result(f"{name}.{alg}", res)

    @staticmethod
    def _merge(partials: List[Dict]) -> Dict:
        """The reduce across shards: counts add; top-K re-merges by score."""
        out = {"total_count": np.sum([p["total_count"] for p in partials]),
               "keypoint_count": np.sum([p["keypoint_count"]
                                         for p in partials])}
        scores = np.concatenate([p["top_scores"] for p in partials])
        order = np.argsort(-scores, kind="stable")[:partials[0]["top_scores"].shape[0]]
        out["top_scores"] = scores[order]
        for key in ("top_ys", "top_xs", "top_valid", "top_desc"):
            if key in partials[0]:
                cat = np.concatenate([p[key] for p in partials])
                out[key] = cat[order]
        out["per_tile_count"] = np.concatenate(
            [p["per_tile_count"] for p in partials])
        return out

    def _alg_counts(self, done: List[str], alg: str) -> Dict[str, int]:
        return {n: int(self.store.get_result(f"{n}.{alg}")["total_count"])
                for n in done}

    def summary(self) -> Dict:
        """Progress + feature counts: per-bundle ``counts`` and the
        ``grand_total`` for single-algorithm jobs; the same nested under
        ``per_algorithm`` for multi-algorithm jobs."""
        done = [n for n, d in self.manifest.done.items() if d]
        base = {"algorithm": self.algorithm, "bundles_done": len(done),
                "bundles_total": len(self.manifest.bundle_names)}
        if len(self.algorithms) == 1:
            counts = self._alg_counts(done, self.algorithm)
            return {**base, "counts": counts,
                    "grand_total": sum(counts.values())}
        per_alg = {}
        for alg in self.algorithms:
            counts = self._alg_counts(done, alg)
            per_alg[alg] = {"counts": counts,
                            "grand_total": sum(counts.values())}
        return {**base, "per_algorithm": per_alg,
                "grand_total": sum(p["grand_total"]
                                   for p in per_alg.values())}
