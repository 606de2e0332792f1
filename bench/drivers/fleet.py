"""Driver of the tile-service fleet: ``serve/fleet.py::Fleet`` with its
thread replicas, one per device the process sees, behind
``serve/router.py::Router``, fed by an open-loop schedule from one
generator thread.

Set-up builds the fleet from the configuration (each replica warms its
programs on its own device as it joins), starts the fleet's liveness and
autoscale ticks (the autoscaler pinned at the replica count), makes the
tile pool from the seed, and plays untimed prefixes of the cell's
traffic as fast as the router takes them, until one runs without
compiling or loading a program.  The window plays ``--seconds`` of the
schedule through ``Fleet.submit``; each request is timed from its due
time to when its replica resolved its last tile, stamped by the
benchmark's own callback on the tile's future (``drivers/service.py``'s
``Stamps``).  The check compares a sample of the window's answers with
the plain reference, as the service cell does; the run is also not
correct unless every replica answered a checked request and the
replicas ran on as many distinct devices as there are replicas."""
from __future__ import annotations

import threading
import time

import numpy as np

from bench import compare, gen, scene
from bench.drivers.service import (DIFET_KEYS, LATE_WAIT_S, Stamps,
                                   latencies)
from bench.harness import log, memory_peak


def play(fleet, reqs, pool, t0: float, paced: bool, keep=()) -> tuple:
    """Submit ``reqs`` through the router from a generator thread: open
    loop at ``t0 + r.t`` (wall clock) when ``paced``, else as fast as the
    router takes them.  Returns the ``Stamps``, each request's lateness
    (seconds) and the fleet handles of the requests in ``keep``."""
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
                    h = fleet.submit(pool.tile(r.tile), r.algorithms)
            except Exception:           # shed: counts as missing
                stamps.refuse(i)
                continue
            stamps.watch(i, h._req.handle, time.time())
            if i in keep:
                kept[i] = h
    th = threading.Thread(target=gen_loop, name="bench-generator")
    th.start()
    th.join()
    return stamps, late, kept


def snapshot(fleet) -> dict:
    """The router's and each replica's counters now."""
    s = fleet.router.stats()
    return {"route_s": s["route_s"], "admitted": s["submitted"],
            "replicas": {n: {"items": r["scheduler"]["items"],
                             "batches": r["batches"],
                             "cache_hits": r["cache_hits"],
                             "cache_misses": r["cache_misses"],
                             "submitted": r["submitted"],
                             "shed": r["shed"], "busy_s": r["busy_s"],
                             "device": r["device"]}
                         for n, r in s["replicas"].items()}}


def window_counts(a: dict, b: dict) -> dict:
    """What the window added between two ``snapshot``s: per replica, and
    summed over replicas under the service cell's names."""
    per = {n: {k: b["replicas"][n][k] - a["replicas"][n][k]
               for k in ("items", "batches", "cache_hits", "cache_misses",
                         "submitted", "shed", "busy_s")}
           for n in b["replicas"]}
    total = {k: sum(p[k] for p in per.values())
             for k in ("items", "batches", "cache_hits", "cache_misses",
                       "submitted", "shed")}
    return {"stats": total, "per_replica": per,
            "route_s": b["route_s"] - a["route_s"],
            "admitted": b["admitted"] - a["admitted"]}


def build(c: dict, sets, lease_dir):
    from repro.configs.difet_paper import DifetConfig
    from repro.serve import Fleet, FleetConfig, RouterConfig, ServeConfig
    (bucket,) = c["buckets"]
    base = DifetConfig(tile=bucket, **{k: c[k] for k in DIFET_KEYS})
    serve = ServeConfig(
        base=base, buckets=(bucket,), max_batch=c["max_batch"],
        max_batch_delay_s=c["max_batch_delay_s"],
        max_pending=c["max_pending"], cache_entries=c["cache_entries"],
        use_pallas=c["use_pallas"])
    rc = c["router"]
    router = RouterConfig(
        max_global_pending=rc["max_global_pending"],
        spill_queue_threshold=rc["spill_queue_threshold"],
        vnodes=rc["vnodes"],
        tenant_rate=(float("inf") if rc["tenant_rate"] is None
                     else rc["tenant_rate"]))
    live, auto = c["liveness"], c["autoscaler"]
    return Fleet(FleetConfig(
        serve=serve, router=router, initial_replicas=c["replicas"],
        min_replicas=auto["min_replicas"], max_replicas=auto["max_replicas"],
        warm_algorithm_sets=tuple(sets), lease_dir=str(lease_dir),
        lease_ttl_s=live["lease_ttl_s"],
        autoscale_interval_s=live["autoscale_interval_s"]))


def run(ctx) -> dict:
    import jax

    c, tr = ctx.config, ctx.traffic
    t = time.perf_counter()
    (bucket,) = c["buckets"]
    sets = [tuple(s) for s in tr["algorithm_sets"]]
    fleet = build(c, sets, ctx.workdir / "leases")
    fleet.start_autoscaler()
    reps = sorted(fleet.replicas.values(), key=lambda r: r.name)
    devices = [r.device for r in reps]
    t = ctx.step(f"fleet ({len(reps)} replicas on devices "
                 f"{[d.id for d in devices]})", t)

    rng = np.random.RandomState(scene.sub_seed(ctx.seed, 0))
    n_window = int(round(tr["rate"] * ctx.seconds))
    size = tr["prefix_requests"] * 4 + n_window
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
        first += len(pre)
        with jax.profiler.TraceAnnotation("bench.warm"):
            play(fleet, pre, pool, time.time(), False)[0].wait(
                time.time() + LATE_WAIT_S)
        if ctx.clock.programs == before:
            break
    t = ctx.step(f"prefix ({warm} x {tr['prefix_requests']} requests)", t)

    reqs = gen.schedule(tr, ctx.seconds, size, rng, first_tile=first)
    pick = sorted(rng.choice(len(reqs), min(tr["check_requests"],
                                            len(reqs)), replace=False))
    s0 = snapshot(fleet)
    with ctx.window():
        t0 = time.time() + 0.01
        stamps, late, kept = play(fleet, reqs, pool, t0, True, keep=pick)
        lat, missing = latencies(reqs, t0, stamps,
                                 t0 + ctx.seconds + LATE_WAIT_S)
    counts = window_counts(s0, snapshot(fleet))
    peak = memory_peak(devices)
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
        f"missing ({lost} accepted and not answered); latency p50 "
        f"{np.percentile(lat, 50) * 1e3:.3f} ms, p95 "
        f"{np.percentile(lat, 95) * 1e3:.3f} ms, max "
        f"{lat.max() * 1e3:.3f} ms; last fifth's median over the first's "
        f"{growth:.3f}")
    log(f"router: {counts['admitted']} admitted, "
        f"{counts['route_s'] * 1e3 / max(counts['admitted'], 1):.4f} ms "
        f"of its own a request")
    for r in reps:
        p = counts["per_replica"][r.name]
        log(f"{r.name} on device {r.device.id}: {p['items']} items in "
            f"{p['batches']} steps, runner busy {p['busy_s']:.3f} s")
    answers = {i: None if missing[i] else kept[i].result() for i in pick}
    answered_by = {kept[i].replica for i in pick if answers[i] is not None}
    fleet.close()

    # ---- check: a sample of the window's answers against the reference
    t = time.perf_counter()
    ref = ctx.reference()
    algs = sorted({a for s in sets for a in s})
    tiles, headers = zip(*(scene.tile_scene(
        np.asarray(pool.tile(reqs[i].tile)), bucket, c["halo"])
        for i in pick))
    tiles, headers = np.concatenate(tiles), np.concatenate(headers)
    p = {**c, "tile": bucket}
    dev = devices[0]
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
    distinct = len({d.id for d in devices})
    every = answered_by == {r.name for r in reps}
    log(f"check: {len(pick)} answers against the reference in "
        f"{time.perf_counter() - t:.1f} s; answered by "
        f"{len(answered_by)} of {len(reps)} replicas, on {distinct} "
        f"distinct devices")
    return {"correct": (ok and lost == 0 and every
                        and distinct == len(reps) == c["replicas"]),
            "checks": checks, "attempted": len(reqs), "failed": failed,
            "memory_peak_bytes": peak, "latency_s": lat.tolist(),
            "late_s": late.tolist(), "growth": growth,
            "max_batch": c["max_batch"], "numbers": numbers,
            "keypoints": tally.keypoints(), **counts,
            "replica_items": {n: p["items"]
                              for n, p in counts["per_replica"].items()},
            "replica_steps": {n: p["batches"]
                              for n, p in counts["per_replica"].items()},
            "replica_devices": {r.name: r.device.id for r in reps}}
