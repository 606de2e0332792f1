"""Driver of the tile service: an in-process ``serve/api.py::FeatureService``
on one chip, fed by an open-loop schedule from a generator thread.

Set-up builds the service from the configuration, warms its programs for
the mix's algorithm sets (``FeatureService.warmup``), makes the tile
pool from the seed, and plays an untimed prefix of the cell's own
traffic as fast as the service takes it (for a hot mix this fills the
result cache), again until a prefix runs without compiling or loading a
program.  The window plays ``--seconds`` of the schedule at the mix's
rate; each request is timed from its due time to when the service
resolved its last tile, stamped by the benchmark's own callback on the
tile's future (it runs in the runner thread once the item is sliced and
cached), or for an answer the cache gave whole, to the return of
``submit``.  A request that is shed, fails or never comes counts as
missing and waits the longest; one that the service accepted and never
answered, or answered with an error, also makes the run not correct
(the configuration's guarantee: every accepted request is answered).  The benchmark keeps per request only
numbers in arrays, and the handles of the sample it checks (drawn from
the seed before the window), so that its own records do not feed the
interpreter's garbage collector, which stops the service's threads.  The
check compares that sample of the window's answers with the plain
reference."""
from __future__ import annotations

import functools
import threading
import time

import numpy as np

from bench import compare, gen, scene
from bench.harness import log, memory_peak

DIFET_KEYS = ("halo", "harris_k", "harris_threshold", "shi_tomasi_threshold",
              "fast_threshold", "fast_arc", "surf_hessian_threshold",
              "n_octaves", "scales_per_octave", "sift_contrast_threshold",
              "sift_edge_threshold", "brief_n_bits", "brief_patch",
              "orb_n_features", "max_keypoints_per_tile", "dtype")
LATE_WAIT_S = 60.0          # how long past the close an answer may come


class Stamps:
    """When the service resolved each request's last tile, by the
    benchmark's clock: a callback on each tile's future stamps
    ``time.time()`` in the thread that resolves it.  A request that the
    cache answered whole has no future and is done when ``submit``
    returned.  One lock and a few arrays for all requests."""

    def __init__(self, n: int):
        self.at = np.full(n, np.nan)        # done, or NaN
        self.left = np.zeros(n, np.int64)   # tiles not yet resolved
        self.failed = np.zeros(n, bool)
        self.refused = np.zeros(n, bool)    # shed by ``submit``
        self.lock = threading.Lock()

    def watch(self, i: int, handle, end: float) -> None:
        futures = [p.future for p in handle._parts if p.future is not None]
        with self.lock:
            self.at[i], self.left[i] = end, len(futures)
        for f in futures:
            f.add_done_callback(functools.partial(self._one, i))

    def _one(self, i: int, future) -> None:
        now = time.time()
        with self.lock:
            if future.cancelled() or future.exception() is not None:
                self.failed[i] = True
            self.at[i] = max(self.at[i], now)
            self.left[i] -= 1

    def refuse(self, i: int) -> None:
        with self.lock:
            self.refused[i] = True

    def wait(self, deadline: float) -> None:
        """Until every request is resolved, or ``deadline``."""
        while time.time() < deadline:
            with self.lock:
                if not self.left.any():
                    return
            time.sleep(0.005)

    def done(self) -> np.ndarray:
        with self.lock:
            return ~np.isnan(self.at) & (self.left == 0) & ~self.failed \
                & ~self.refused


def play(svc, reqs, pool, t0: float, paced: bool, keep=()) -> tuple:
    """Submit ``reqs`` from a generator thread: open loop at ``t0 + r.t``
    (wall clock) when ``paced``, else as fast as the queue takes them.
    Returns the ``Stamps``, each request's lateness against its due time
    (seconds) and the handles of the requests in ``keep``."""
    import jax
    stamps, late, kept = Stamps(len(reqs)), np.zeros(len(reqs)), {}
    keep = set(keep)

    def gen_loop():
        for i, r in enumerate(reqs):
            due = t0 + r.t
            wait = due - time.time()
            if paced and wait > 0:
                time.sleep(wait)
            late[i] = time.time() - due
            try:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    h = svc.submit(pool.tile(r.tile), r.algorithms,
                                   block=not paced)
            except Exception:           # shed or refused: counts as missing
                stamps.refuse(i)
                continue
            stamps.watch(i, h, time.time())
            if i in keep:
                kept[i] = h
    th = threading.Thread(target=gen_loop, name="bench-generator")
    th.start()
    th.join()
    return stamps, late, kept


def latencies(reqs, t0: float, stamps: Stamps, deadline: float):
    """Each request's latency (seconds), from its due time to its
    ``Stamps`` time, or to ``deadline`` if it is missing; and which are
    missing.  Waits for answers until ``deadline``."""
    import jax
    with jax.profiler.TraceAnnotation("bench.collect"):
        stamps.wait(deadline)
    done = stamps.done()
    due = t0 + np.array([r.t for r in reqs])
    return np.where(done, stamps.at, deadline) - due, ~done


def _delta(a: dict, b: dict) -> dict:
    keys = ("cache_hits", "cache_misses", "batches", "submitted", "shed")
    return {k: b[k] - a[k] for k in keys} | {
        "items": b["scheduler"]["items"] - a["scheduler"]["items"]}


def run(ctx) -> dict:
    import jax
    from repro.configs.difet_paper import DifetConfig
    from repro.serve import FeatureService, ServeConfig

    c, tr = ctx.config, ctx.traffic
    t = time.perf_counter()
    (bucket,) = c["buckets"]
    base = DifetConfig(tile=bucket, **{k: c[k] for k in DIFET_KEYS})
    svc = FeatureService(ServeConfig(
        base=base, buckets=(bucket,), max_batch=c["max_batch"],
        max_batch_delay_s=c["max_batch_delay_s"],
        max_pending=c["max_pending"], cache_entries=c["cache_entries"],
        use_pallas=c["use_pallas"]))
    sets = [tuple(s) for s in tr["algorithm_sets"]]
    programs = svc.warmup(sets)
    t = ctx.step(f"programs ({programs})", t)

    rng = np.random.RandomState(scene.sub_seed(ctx.seed, 0))
    n_window = int(round(tr["rate"] * ctx.seconds))
    hot = "hot_weight" in tr
    size = tr["pool_size"] if hot else \
        tr["prefix_requests"] * 4 + n_window
    stride = tr["pool_stride"]
    side = bucket + stride * (int(np.ceil(np.sqrt(size))) - 1)
    img = scene.synthetic_scene(side, side, seed=scene.sub_seed(ctx.seed, 1))
    pool = gen.WindowPool(img, bucket, size, stride, rng)
    t = ctx.step(f"pool ({size} tiles)", t)

    first = 0
    for warm in range(1, 5):
        before = ctx.clock.programs
        pre = gen.schedule(tr, tr["prefix_requests"] / tr["rate"], size,
                           rng, first_tile=first)
        first += 0 if hot else len(pre)
        with jax.profiler.TraceAnnotation("bench.warm"):
            play(svc, pre, pool, time.time(), False)[0].wait(
                time.time() + LATE_WAIT_S)
        if ctx.clock.programs == before:
            break
    t = ctx.step(f"prefix ({warm} x {tr['prefix_requests']} requests)", t)

    reqs = gen.schedule(tr, ctx.seconds, size, rng, first_tile=first)
    pick = sorted(rng.choice(len(reqs), min(tr["check_requests"],
                                            len(reqs)), replace=False))
    s0 = svc.stats()
    with ctx.window():
        t0 = time.time() + 0.01
        stamps, late, kept = play(svc, reqs, pool, t0, True, keep=pick)
        lat, missing = latencies(reqs, t0, stamps,
                                 t0 + ctx.seconds + LATE_WAIT_S)
    s1 = svc.stats()
    peak = memory_peak(jax.devices()[:1])
    failed = int(missing.sum())
    lost = int((missing & ~stamps.refused).sum())
    fifth = max(1, len(lat) // 5)
    growth = float(np.median(lat[-fifth:]) / max(np.median(lat[:fifth]),
                                                  1e-9))
    log(f"generator: {len(reqs)} requests, late by p50 "
        f"{np.percentile(late, 50) * 1e3:.3f} ms, p99 "
        f"{np.percentile(late, 99) * 1e3:.3f} ms, max "
        f"{late.max() * 1e3:.3f} ms")
    log(f"window: {len(reqs)} requests over {ctx.seconds} s, {failed} "
        f"missing ({lost} accepted and not answered); latency p50 {np.percentile(lat, 50) * 1e3:.3f} ms, p95 "
        f"{np.percentile(lat, 95) * 1e3:.3f} ms, max "
        f"{lat.max() * 1e3:.3f} ms; last fifth's median over the first's "
        f"{growth:.3f}")
    answers = {i: None if missing[i] else kept[i].result() for i in pick}
    svc.close()

    # ---- check: a sample of the window's answers, hits and misses alike
    t = time.perf_counter()
    ref = ctx.reference()
    algs = sorted({a for s in sets for a in s})
    tiles, headers = zip(*(scene.tile_scene(
        np.asarray(pool.tile(reqs[i].tile)), bucket, c["halo"])
        for i in pick))
    tiles, headers = np.concatenate(tiles), np.concatenate(headers)
    p = {**c, "tile": bucket}
    dev = jax.devices()[0]
    want = ref.features(tiles, headers, p, algs, "float32", device=dev)
    low = (ref.features(tiles, headers, p, algs, "bfloat16", device=dev)
           if ctx.control else None)
    pairs = []
    for j, i in enumerate(pick):
        resp = answers[i]
        for a in reqs[i].algorithms:
            w = ref.request_result(want[a], j)
            if ctx.control:
                g = ref.request_result(low[a], j)
            elif resp is None:
                g = {"total_count": -1, "top_valid": np.zeros(0, bool),
                     "top_ys": np.zeros(0), "top_xs": np.zeros(0)}
            else:
                g = resp.results[a]
            pairs.append((a, g, w))
    tally = compare.tally(pairs)
    numbers = tally.numbers()
    ok, checks = compare.checks(numbers, c["limits"])
    log(f"check: {len(pick)} answers against the reference in "
        f"{time.perf_counter() - t:.1f} s")
    return {"correct": ok and lost == 0, "checks": checks,
            "attempted": len(reqs), "failed": int(failed),
            "memory_peak_bytes": peak, "latency_s": lat.tolist(),
            "late_s": late.tolist(), "stats": _delta(s0, s1),
            "growth": growth,
            "max_batch": c["max_batch"], "numbers": numbers,
            "keypoints": tally.keypoints()}
