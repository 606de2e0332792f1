"""Benchmark-gated matcher dispatch: measure once per shape-bucket, route
every later call to the winning implementation.

The fastest formulation of the matcher is a *backend property* (packed
chunked scans win on TPU where HBM traffic dominates; one fused [Q, K]
block wins on CPU XLA; interpret-mode Pallas is never a perf path), so
hardcoding any single choice loses somewhere.
Instead, `ops.match_best2` asks :func:`choose_path`, which runs a tiny
one-shot microbenchmark per ``(metric, backend, shape-bucket)`` the first
time a bucket is seen, persists the verdict to a small on-disk JSON
cache, and answers from memory afterwards — a call site on a backend
where one path regresses silently gets the fast one.

Buckets round (nq, nk) up to powers of two (descriptor width stays
exact), so the measurement cost is O(log^2) in shape space.  Probe
arrays are capped (`PROBE_NQ_CAP` / `PROBE_NK_CAP`): beyond the cap
every candidate is linear in the same streamed dimension, so the capped
contest ranks them correctly without materializing a million-row probe.

Candidate paths (see `kernels/matcher.py` for the implementations):

====================  =======================================================
``jnp_full``          one [Q, K] distance block (`best2_full`)
``jnp_stream``        lax.scan over DB chunks, carried registers
                      (`best2_stream`)
``pallas_resident``   whole-DB-in-VMEM kernel (`match_pallas`); TPU only,
                      and only when the DB fits the VMEM budget
``pallas_stream``     tiled-DB streaming kernel (`match_pallas_stream`);
                      TPU only
====================  =======================================================

Databases larger than `FULL_MAX_ROWS` drop the materializing candidates
(``jnp_full`` / ``pallas_resident``) outright — a million-row [Q, K]
block is a memory hazard regardless of speed — which is what lets one
query batch scan millions of descriptors through the streaming paths.

The cache file lives at ``$DIFET_DISPATCH_CACHE`` (default
``~/.cache/difet/matcher_dispatch.json``); delete it to re-measure, e.g.
after a driver or XLA upgrade.
"""
from __future__ import annotations

import functools
import json
import os
import threading
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import matcher as _matcher
from repro.obs import metrics as obs_metrics
from repro.obs import profile as obs_profile

JNP_PATHS = ("jnp_full", "jnp_stream")
PALLAS_PATHS = ("pallas_resident", "pallas_stream")
MATCH_PATHS = JNP_PATHS + PALLAS_PATHS

# beyond this many DB rows the [Q, K] block / resident-DB candidates are
# excluded (memory hazard), leaving only the streaming paths
FULL_MAX_ROWS = 1 << 17
# probe-array caps: the microbenchmark never materializes more than this
PROBE_NQ_CAP = 512
PROBE_NK_CAP = 1 << 14
_PROBE_REPS = 3

CACHE_ENV = "DIFET_DISPATCH_CACHE"
_lock = threading.Lock()
_memory: Dict[str, str] = {}        # bucket key -> chosen path (per process)
# measurement counter, exposed for tests asserting cache hit/miss behavior
measure_count = 0


def cache_path() -> str:
    """Location of the on-disk dispatch cache (``$DIFET_DISPATCH_CACHE``
    or ``~/.cache/difet/matcher_dispatch.json``)."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "difet",
                        "matcher_dispatch.json")


def clear_memory_cache() -> None:
    """Drop the in-process bucket->path memo (the disk cache survives);
    mainly for tests that repoint ``$DIFET_DISPATCH_CACHE``."""
    with _lock:
        _memory.clear()


def _load_disk() -> Dict[str, dict]:
    try:
        with open(cache_path()) as f:
            d = json.load(f)
        return d if isinstance(d, dict) else {}
    except (OSError, ValueError):
        return {}


def _store_disk(key: str, entry: dict) -> None:
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        d = _load_disk()
        d[key] = entry
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(d, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass                    # read-only FS: in-memory memo still works


def _pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def shape_bucket(nq: int, nk: int, d: int) -> Tuple[int, int, int]:
    """Round (nq, nk) up to powers of two; descriptor width stays exact.
    All shapes in a bucket share one measured verdict."""
    return _pow2(max(nq, 1)), _pow2(max(nk, 1)), int(d)


def bucket_key(metric: str, backend: str, nq: int, nk: int, d: int) -> str:
    qb, kb, db = shape_bucket(nq, nk, d)
    return f"{metric}|{backend}|q{qb}|k{kb}|d{db}"


def candidate_paths(metric: str, backend: str, nk: int, d: int,
                    use_pallas: Optional[bool] = None) -> Tuple[str, ...]:
    """Paths eligible for a (metric, backend, DB-size) combination.

    ``use_pallas=True`` restricts to the kernels, ``False`` to the jnp
    formulations, ``None`` lets the benchmark decide among all eligible.
    Pallas candidates require a TPU backend (interpret mode validates
    numerics, not speed); the materializing candidates drop out beyond
    `FULL_MAX_ROWS`.
    """
    big_db = nk > FULL_MAX_ROWS
    jnp_c = ("jnp_stream",) if big_db else JNP_PATHS
    if backend == "tpu":
        from repro.kernels import ops as _ops       # local: avoid cycle at import
        fits = _ops.matcher_fits_vmem(nk, d, metric)
        pallas_c = ("pallas_stream",) if (big_db or not fits) else PALLAS_PATHS
    else:
        pallas_c = ()
    if use_pallas is True:
        return pallas_c or (("pallas_stream",) if backend == "tpu"
                            else jnp_c)
    if use_pallas is False:
        return jnp_c
    return jnp_c + pallas_c


def _probe_arrays(metric: str, nq: int, nk: int, d: int):
    """Deterministic numpy probe inputs (numpy, not jnp: the caller may
    be inside someone else's trace — conversion happens in the probe
    thread, which has no ambient trace)."""
    rng = np.random.RandomState(0)
    if metric == "hamming":
        q = rng.randint(0, 2 ** 32, size=(nq, d),
                        dtype=np.uint64).astype(np.uint32)
        db = rng.randint(0, 2 ** 32, size=(nk, d),
                         dtype=np.uint64).astype(np.uint32)
    else:
        q = rng.randn(nq, d).astype(np.float32)
        db = rng.randn(nk, d).astype(np.float32)
    return q, db, np.ones((nk,), np.bool_)


def _time_call(fn, *args) -> float:
    """Median-of-reps wall time in us, with a *blocking* warm-up so the
    first rep never pays compile or the warm-up's async execution."""
    out = fn(*args)
    jax.tree_util.tree_leaves(out)[0].block_until_ready()
    samples = []
    for _ in range(_PROBE_REPS):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.tree_util.tree_leaves(out)[0].block_until_ready()
        samples.append(time.perf_counter() - t0)
    return float(np.median(samples) * 1e6)


def measure_path(path: str, metric: str, nq: int, nk: int, d: int) -> float:
    """One-shot microbenchmark of one candidate path at a (capped) bucket
    shape; returns us per call.

    The probe runs in a fresh thread: JAX trace state is thread-local, so
    a dispatch decision triggered *inside* someone else's jit trace (the
    usual case — `match_best2` called under a caller's jit) still
    executes its probe jits concretely instead of being inlined into the
    outer trace as tracers.  All probe inputs are built in the thread.
    """
    global measure_count
    measure_count += 1
    obs_metrics.registry().counter("difet.kernel.dispatch_measures").inc()
    nq = min(nq, PROBE_NQ_CAP)
    nk = min(nk, PROBE_NK_CAP)
    box: Dict[str, object] = {}

    def run():
        try:
            q, db, valid = _probe_arrays(metric, nq, nk, d)
            from repro.kernels import ops as _ops
            fn = jax.jit(functools.partial(_ops.match_best2, metric=metric,
                                           path=path))
            box["us"] = _time_call(fn, jnp.asarray(q), jnp.asarray(db),
                                   jnp.asarray(valid))
        except BaseException as e:             # surfaced by the caller
            box["err"] = e

    t = threading.Thread(target=run, name=f"difet-dispatch-probe-{path}")
    t.start()
    t.join()
    if "err" in box:
        raise box["err"]                       # type: ignore[misc]
    return float(box["us"])                    # type: ignore[arg-type]


def choose_path(metric: str, nq: int, nk: int, d: int, *,
                backend: Optional[str] = None,
                use_pallas: Optional[bool] = None) -> str:
    """The dispatch decision: fastest measured path for this bucket.

    First call per (metric, backend, bucket) runs the microbenchmark and
    persists the verdict; later calls answer from the in-process memo or
    the disk cache.  Single-candidate combinations skip measurement.
    """
    backend = backend or jax.default_backend()
    cands = candidate_paths(metric, backend, nk, d, use_pallas)
    if len(cands) == 1:
        return cands[0]
    abbrev = {"jnp_full": "jf", "jnp_stream": "js",
              "pallas_resident": "pr", "pallas_stream": "ps"}
    key = bucket_key(metric, backend, nq, nk, d) \
        + "|" + "".join(sorted(abbrev[c] for c in cands))
    with _lock:
        hit = _memory.get(key)
    if hit is not None:
        return hit
    disk = _load_disk().get(key)
    if isinstance(disk, dict) and disk.get("path") in cands:
        with _lock:
            _memory[key] = disk["path"]
        return disk["path"]
    qb, kb, db = shape_bucket(nq, nk, d)
    timings = {c: measure_path(c, metric, qb, kb, db) for c in cands}
    best = min(timings, key=timings.get)
    with _lock:
        _memory[key] = best
    for c, us in timings.items():              # probe wall → kernel profile
        obs_profile.record_call(f"dispatch:{metric}:{c}:q{qb}k{kb}d{db}",
                                us * 1e-6)
    # full provenance: enough to audit WHY this bucket routes where it
    # does without re-measuring (launch/obs.py --explain-dispatch)
    _store_disk(key, {"path": best, "us": timings,
                      "probe": [min(qb, PROBE_NQ_CAP),
                                min(kb, PROBE_NK_CAP), db],
                      "metric": metric, "backend": backend,
                      "bucket": [qb, kb, db],
                      "candidates": sorted(cands)})
    return best


def explain() -> Dict[str, dict]:
    """Decoded view of the on-disk dispatch cache: per bucket key, the
    winning path, its margin over the runner-up, and the full candidate
    timing table (``launch/obs.py --explain-dispatch`` renders this).
    Entries written before provenance fields existed decode with
    ``metric``/``backend`` parsed from the key."""
    out: Dict[str, dict] = {}
    for key, entry in sorted(_load_disk().items()):
        if not isinstance(entry, dict) or "path" not in entry:
            continue
        parts = key.split("|")
        row = {"path": entry["path"],
               "metric": entry.get("metric", parts[0]),
               "backend": entry.get("backend",
                                    parts[1] if len(parts) > 1 else "?"),
               "bucket": entry.get("bucket"),
               "probe": entry.get("probe"),
               "candidates": entry.get("candidates",
                                       sorted(entry.get("us", {}))),
               "us": dict(entry.get("us", {}))}
        us = row["us"]
        if len(us) >= 2:
            ranked = sorted(us.items(), key=lambda kv: kv[1])
            row["margin"] = (ranked[1][1] / ranked[0][1]
                             if ranked[0][1] > 0 else float("inf"))
        out[key] = row
    return out
