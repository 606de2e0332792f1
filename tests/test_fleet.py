"""Fleet serving subsystem: trace generator, consistent-hash routing,
admission control, tiered cache, replica lifecycle, lease liveness,
autoscaling, and chaos (kill mid-flight with bit-parity)."""
import dataclasses
import functools
import threading
import time

import numpy as np
import pytest

import jax

from repro.configs.difet_paper import DifetConfig
from repro.core import engine
from repro.data.landsat import synthetic_scene
from repro.serve import (DiskCacheTier, FeatureService, Fleet, FleetConfig,
                         HashRing, Router, RouterConfig, ServeConfig, Shed,
                         TieredResultCache, TokenBucket, TraceConfig,
                         make_trace, scene_key, tile_pool)
from repro.serve.fleet import DEAD, DRAINING, READY, RETIRED
from repro.serve.router import (SHED_CLOSED, SHED_FLEET_SATURATED,
                                SHED_NO_REPLICA, SHED_TENANT_THROTTLED)

BASE = DifetConfig(tile=32, halo=8, max_keypoints_per_tile=16)


def fleet_cfg(n, *, cache_dir=None, lease_dir=None, lease_ttl_s=5.0,
              max_batch=4, max_pending=1024, cache_entries=0,
              max_batch_delay_s=0.005, min_replicas=1, max_replicas=None,
              scale_up=16.0, scale_down=2.0, grace=3, slo_p99_s=0.5,
              router=None) -> FleetConfig:
    return FleetConfig(
        serve=ServeConfig(base=BASE, buckets=(32,), max_batch=max_batch,
                          max_batch_delay_s=max_batch_delay_s,
                          max_pending=max_pending,
                          cache_entries=cache_entries),
        router=router or RouterConfig(),
        initial_replicas=n, min_replicas=min_replicas,
        max_replicas=max_replicas or max(n, 2),
        warm_algorithm_sets=(("harris",),),
        cache_dir=str(cache_dir) if cache_dir else None,
        lease_dir=str(lease_dir) if lease_dir else None,
        lease_ttl_s=lease_ttl_s,
        slo_p99_s=slo_p99_s,
        scale_up_queue_per_replica=scale_up,
        scale_down_queue_per_replica=scale_down,
        scale_down_grace_ticks=grace)


def direct(gray, algs=("harris",)):
    """Unrouted reference: jitted extract_features_multi on the padded
    tile (the parity oracle every served result must match bitwise)."""
    svc = FeatureService(ServeConfig(base=BASE, buckets=(32,)))
    try:
        bucket = svc.table.bucket_for(*gray.shape)
        tile, header = svc.table.pad_to_bucket(gray, bucket)
        fn = jax.jit(functools.partial(engine.extract_features_multi,
                                       algorithms=tuple(sorted(algs)),
                                       cfg=svc.table.cfg_for(bucket)))
        return {alg: {k: np.asarray(v) for k, v in res.items()}
                for alg, res in fn(tile[None], header[None]).items()}
    finally:
        svc.close()


def assert_results_equal(a, b):
    assert set(a) == set(b)
    for alg in a:
        assert set(a[alg]) == set(b[alg])
        for k in a[alg]:
            x, y = np.asarray(a[alg][k]), np.asarray(b[alg][k])
            assert x.shape == y.shape and x.dtype == y.dtype, (alg, k)
            assert np.array_equal(x, y), (alg, k)


# ---- trace generator -------------------------------------------------------

def test_trace_deterministic_and_skewed():
    cfg = TraceConfig(n_requests=600, seed=7, arrival="poisson", rate=500.0,
                      unique_scenes=16, hot_fraction=0.125, hot_weight=0.7,
                      tenants=("a", "b"), tenant_weights=(0.75, 0.25))
    t1, t2 = make_trace(cfg), make_trace(cfg)
    assert t1 == t2                       # byte-identical replays
    ts = [ev.t for ev in t1]
    assert all(b >= a for a, b in zip(ts, ts[1:]))   # arrivals ordered
    # mean rate within 2x of nominal (poisson, 600 samples)
    assert 0.5 * 600 / 500.0 < ts[-1] < 2.0 * 600 / 500.0
    # hot set (2 of 16 scenes) draws ~70% of the mass
    hot_frac = np.mean([ev.scene < 2 for ev in t1])
    assert 0.55 < hot_frac < 0.85
    tenant_a = np.mean([ev.tenant == "a" for ev in t1])
    assert 0.6 < tenant_a < 0.9


def test_trace_burst_arrivals_cluster():
    cfg = TraceConfig(n_requests=400, seed=1, arrival="burst", rate=200.0,
                      burst_factor=4.0, burst_fraction=0.25)
    gaps = np.diff([0.0] + [ev.t for ev in make_trace(cfg)])
    mean_gap = 1.0 / 200.0
    assert gaps.min() < 0.5 * mean_gap    # spikes are genuinely faster
    assert gaps.max() > mean_gap          # calm segments slower than mean
    # long-run mean stays near the nominal rate
    assert 0.3 * mean_gap < gaps.mean() < 3.0 * mean_gap


def test_tile_pool_shared_across_same_seed():
    a = tile_pool(TraceConfig(n_requests=1, seed=5, unique_scenes=3))
    b = tile_pool(TraceConfig(n_requests=99, seed=5, unique_scenes=3))
    for k in a:
        assert np.array_equal(a[k], b[k])   # parity checks depend on this


# ---- consistent hashing ----------------------------------------------------

def test_hash_ring_minimal_remap_and_balance():
    ring = HashRing(vnodes=64)
    for name in ("r1", "r2", "r3", "r4"):
        ring.add(name)
    keys = [f"scene-{i}" for i in range(400)]
    before = {k: ring.lookup(k) for k in keys}
    share = {n: sum(1 for v in before.values() if v == n)
             for n in ring.names}
    assert all(s > 0.05 * len(keys) for s in share.values())   # balanced-ish
    ring.remove("r3")
    after = {k: ring.lookup(k) for k in keys}
    for k in keys:
        if before[k] != "r3":
            assert after[k] == before[k]     # only r3's keys remapped
        else:
            assert after[k] != "r3"
    ring.add("r3")
    assert {k: ring.lookup(k) for k in keys} == before   # and they return


def test_token_bucket_throttles_and_refills():
    tb = TokenBucket(rate=50.0, burst=3)
    takes = [tb.take()[0] for _ in range(4)]
    assert takes == [True, True, True, False]
    ok, retry = tb.take()
    assert not ok and retry > 0
    time.sleep(retry + 0.05)
    assert tb.take()[0]                   # refilled
    assert TokenBucket(float("inf"), 1).take() == (True, 0.0)


# ---- router admission: typed sheds ----------------------------------------

def test_router_typed_sheds():
    img = np.zeros((8, 8), np.float32)
    r = Router(RouterConfig(tenant_limits={"limited": (0.001, 1.0)}))
    with pytest.raises(Shed) as e:        # empty pool
        r.submit(img, ("harris",))
    assert e.value.reason == SHED_NO_REPLICA
    r._bucket("limited").take()           # burn the only token (burst=1)
    with pytest.raises(Shed) as e:
        r.submit(img, ("harris",), tenant="limited")
    assert e.value.reason == SHED_TENANT_THROTTLED
    assert e.value.tenant == "limited" and e.value.retry_after_s > 0
    assert isinstance(e.value, Shed)      # and a ServiceOverloaded subclass
    from repro.serve import ServiceOverloaded
    assert isinstance(e.value, ServiceOverloaded)

    r2 = Router(RouterConfig(max_global_pending=0))
    with pytest.raises(Shed) as e:
        r2.submit(img, ("harris",))
    assert e.value.reason == SHED_FLEET_SATURATED

    r.close()
    with pytest.raises(Shed) as e:
        r.submit(img, ("harris",))
    assert e.value.reason == SHED_CLOSED
    s = r.stats()
    assert s["shed_total"] == sum(s["shed"].values()) >= 3


# ---- tiered cache ----------------------------------------------------------

def test_disk_tier_roundtrip_bit_exact(tmp_path):
    tier = DiskCacheTier(tmp_path)
    key = ("digest:0:0", "harris", "cfg")
    val = {"top_scores": np.arange(6, dtype=np.float32).reshape(2, 3),
           "total_count": np.array(7, np.int32),          # 0-d leaf
           "top_valid": np.array([True, False])}
    tier.put(key, val)
    out = tier.get(key)
    assert set(out) == set(val)
    for k in val:
        assert out[k].shape == np.asarray(val[k]).shape
        assert out[k].dtype == np.asarray(val[k]).dtype
        assert np.array_equal(out[k], val[k])
        assert not out[k].flags.writeable
    assert tier.get(("other",)) is None and tier.misses == 1


def test_disk_tier_torn_entry_is_a_miss(tmp_path):
    tier = DiskCacheTier(tmp_path)
    key = ("k", "harris", "cfg")
    path = tier.path_for(key)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(b"not an npz: crashed writer")
    assert tier.get(key) is None          # torn entry reads as a miss
    assert not path.exists()              # and is removed
    tier.put(key, {"a": np.ones((2,), np.float32)})
    assert tier.get(key) is not None      # slot is reusable


def test_tiered_cache_warms_a_fresh_local(tmp_path):
    c1 = TieredResultCache(8, tmp_path)
    c2 = TieredResultCache(8, tmp_path)   # fresh LRU, same disk tier
    key = ("d", "harris", "cfg")
    c1.put(key, {"x": np.full((3,), 2.5, np.float32)})
    hit = c2.get(key)                     # served off disk
    assert hit is not None and c2.disk.hits == 1
    assert np.array_equal(hit["x"], np.full((3,), 2.5, np.float32))
    c2.get(key)
    assert c2.local.hits == 1             # promoted: second probe is local
    assert c2.hits == 2 and c2.misses == 0


# ---- fleet routing + lifecycle --------------------------------------------

def test_affinity_routes_same_scene_to_one_replica():
    fleet = Fleet(fleet_cfg(2, cache_entries=128))
    try:
        tile = synthetic_scene(32, 32, 42)
        for _ in range(6):
            fleet.submit(tile, ("harris",), scene_key="scene-X").result(60)
        s = fleet.stats()
        assert s["routed_affinity"] == 6 and s["routed_spill"] == 0
        per = [r["submitted"] for r in s["replicas"].values()]
        assert sorted(per) == [0, 6]      # all six on the affinity replica
    finally:
        fleet.close()


def test_same_digest_in_flight_on_two_replicas_is_consistent(tmp_path):
    """The same tile computed concurrently on two replicas (forced routing)
    must yield bit-identical results on both, and the shared disk tier
    must converge to one well-formed entry either writer could have
    produced."""
    step_lock = threading.Lock()
    fleet = Fleet(fleet_cfg(2, cache_entries=128, cache_dir=tmp_path),
                  step_lock=step_lock)
    try:
        tile = synthetic_scene(32, 32, 77)
        names = fleet.ready_replicas()
        with step_lock:                   # both in flight simultaneously
            handles = [
                fleet.router._slots[n].service.submit(tile, ("harris",))
                for n in names]
        r = [h.result(60).results for h in handles]
        assert_results_equal(r[0], r[1])
        assert_results_equal(r[0], direct(tile))
        # the tier holds exactly the per-algorithm entries for this tile,
        # whichever replica won the (benign) write race
        assert len(fleet.router._slots[names[0]].service.cache.disk) >= 1
        rerouted = fleet.extract(tile, ("harris",), timeout=60).results
        assert_results_equal(rerouted, r[0])
    finally:
        fleet.close()


def test_drain_then_retire_drops_nothing():
    step_lock = threading.Lock()
    fleet = Fleet(fleet_cfg(2, max_batch=4), step_lock=step_lock)
    try:
        tiles = [synthetic_scene(32, 32, 600 + i) for i in range(12)]
        with step_lock:                   # keep every request in flight
            handles = [fleet.submit(t, ("harris",),
                                    scene_key=f"scene-{i}")
                       for i, t in enumerate(tiles)]
            victim = max(fleet.ready_replicas(),
                         key=lambda n: fleet.router._slots[n]
                         .service.scheduler.queue_depth)
            drainer = threading.Thread(
                target=fleet.drain_replica, args=(victim,))
            drainer.start()
            time.sleep(0.1)               # drain starts while work queued
        drainer.join(60)
        assert not drainer.is_alive()
        results = [h.result(60) for h in handles]   # zero dropped responses
        assert len(results) == len(tiles)
        for t, r in zip(tiles, results):
            assert_results_equal(r.results, direct(t))
        assert fleet.replicas[victim].state == RETIRED
        assert victim not in fleet.router.replica_names()
        # retired replica takes no new work; the fleet still serves
        fleet.extract(tiles[0], ("harris",), timeout=60)
    finally:
        fleet.close()


def test_kill_replica_midflight_readmits_bit_identical():
    """Chaos gate: killing a replica with queued + on-device work loses no
    accepted request, and every response matches the direct engine
    bitwise (re-execution is deterministic)."""
    step_lock = threading.Lock()
    fleet = Fleet(fleet_cfg(2, max_batch=4), step_lock=step_lock)
    try:
        tiles = [synthetic_scene(32, 32, 700 + i) for i in range(10)]
        with step_lock:                   # all work pending/in flight
            handles = [fleet.submit(t, ("harris",),
                                    scene_key=f"scene-{i}")
                       for i, t in enumerate(tiles)]
            victim = max(fleet.ready_replicas(),
                         key=lambda n: fleet.router._slots[n]
                         .service.scheduler.queue_depth)
            fleet.kill_replica(victim)    # re-admission happens in here
        results = [h.result(60) for h in handles]
        assert len(results) == len(tiles)
        for t, r in zip(tiles, results):
            assert_results_equal(r.results, direct(t))
        assert fleet.router.readmitted >= 1
        assert fleet.replicas[victim].state == DEAD
        assert victim not in fleet.router.replica_names()
    finally:
        fleet.close()


def test_stale_lease_detects_silent_crash_and_readmits(tmp_path):
    """A replica whose runner dies without telling anyone: heartbeats
    stop, the lease goes stale after one TTL, and the maintenance tick
    declares it dead + re-admits its outstanding work."""
    fleet = Fleet(fleet_cfg(2, lease_dir=tmp_path, lease_ttl_s=0.5,
                            max_batch=64, max_batch_delay_s=10.0))
    try:
        tile = synthetic_scene(32, 32, 801)
        h = fleet.submit(tile, ("harris",), scene_key="scene-crash")
        victim = next(iter(fleet.router._outstanding.values())).replica
        # simulate a silent crash: the runner dies, the fleet is not told
        fleet.router._slots[victim].service.kill()
        assert fleet.maintenance_tick() == []     # lease still fresh
        assert fleet.replicas[victim].state == READY
        time.sleep(0.6)                           # let the lease expire
        died = fleet.maintenance_tick()
        assert victim in died
        assert fleet.replicas[victim].state == DEAD
        r = h.result(60)                          # re-admitted + served
        assert_results_equal(r.results, direct(tile))
    finally:
        fleet.close()


def test_autoscaler_scales_up_on_depth_and_down_after_grace():
    # slo_p99_s=1e9 mutes the latency policy so only the queue-depth
    # triggers are exercised (the SLO path has its own tests)
    step_lock = threading.Lock()
    fleet = Fleet(fleet_cfg(1, min_replicas=1, max_replicas=2,
                            scale_up=4.0, scale_down=2.0, grace=2,
                            slo_p99_s=1e9),
                  step_lock=step_lock)
    try:
        # 12 tiles: the runner holds up to max_batch=4 in flight, so the
        # *queued* depth the policy sees is still 8 > the threshold of 4
        tiles = [synthetic_scene(32, 32, 900 + i) for i in range(12)]
        with step_lock:                   # queue builds past the watermark
            handles = [fleet.submit(t, ("harris",)) for t in tiles]
            action = fleet.autoscale_tick()
        assert action.startswith("scale_up:")
        assert len(fleet.ready_replicas()) == 2
        for h in handles:
            h.result(60)
        # empty queue: two grace ticks, then drain the idle replica
        assert fleet.autoscale_tick() == "hold"
        action = fleet.autoscale_tick()
        assert action.startswith("scale_down:")
        assert len(fleet.ready_replicas()) == 1
        assert fleet.autoscale_tick() == "hold"   # at min_replicas
        # the surviving replica still serves
        fleet.extract(tiles[0], ("harris",), timeout=60)
    finally:
        fleet.close()


def test_fleet_parity_over_trace(tmp_path):
    """Routed results over a mixed hot-scene trace are bit-identical to
    the direct engine — through cache hits, spills and the disk tier."""
    cfg = TraceConfig(n_requests=24, seed=11, unique_scenes=6,
                      tile_sizes=(32,), algorithm_sets=(("harris",),))
    trace, pool = make_trace(cfg), tile_pool(cfg)
    fleet = Fleet(fleet_cfg(2, cache_entries=128, cache_dir=tmp_path))
    try:
        handles = [fleet.submit(pool[ev.pool_key], ev.algorithms,
                                scene_key=scene_key(ev)) for ev in trace]
        oracle = {}
        for ev, h in zip(trace, handles):
            if ev.pool_key not in oracle:
                oracle[ev.pool_key] = direct(pool[ev.pool_key],
                                             ev.algorithms)
            assert_results_equal(h.result(60).results,
                                 oracle[ev.pool_key])
        s = fleet.stats()
        assert s["submitted"] == len(trace) and s["outstanding"] == 0
    finally:
        fleet.close()


# ---- placement: one device per thread replica --------------------------------

FOUR_DEVICES = r"""
import dataclasses, json, sys
sys.path[:0] = [{root!r}, {root!r} + "/src"]
import jax
import numpy as np
from bench import compare
from bench.refs import difet_features as ref
from bench.scene import tile_scene
from repro.configs.difet_paper import DifetConfig
from repro.data.landsat import synthetic_scene
from repro.serve import Fleet, FleetConfig, ServeConfig

assert len(jax.local_devices()) == 4
base = DifetConfig(tile=32, halo=8, max_keypoints_per_tile=16)
algs = ("harris", "sift")
fleet = Fleet(FleetConfig(
    serve=ServeConfig(base=base, buckets=(32,), max_batch=4,
                      cache_entries=0),
    initial_replicas=4, min_replicas=4, max_replicas=4,
    warm_algorithm_sets=(algs,)))
seen = {{}}                    # replica -> devices of its steps' outputs
for name, rep in fleet.replicas.items():
    cc = rep.service.compile_cache
    def get(bucket, a, real=cc.get, name=name):
        fn = real(bucket, a)
        def step(*args):
            out = fn(*args)
            seen.setdefault(name, set()).update(
                d.id for leaf in jax.tree_util.tree_leaves(out)
                for d in leaf.devices())
            return out
        return step
    cc.get = get
tiles = [synthetic_scene(32, 32, 1500 + i) for i in range(24)]
handles = [fleet.submit(t, algs, scene_key=f"scene-{{i}}")
           for i, t in enumerate(tiles)]
got = [h.result(120) for h in handles]
served = {{h.replica for h in handles}}
stats = fleet.stats()["replicas"]
fleet.close()
p = dataclasses.asdict(base)
batch = [tile_scene(np.asarray(t, np.float32), 32, 8) for t in tiles]
want = ref.features(np.concatenate([b[0] for b in batch]),
                    np.concatenate([b[1] for b in batch]), p, algs)
def same(g, w):
    # the count, which slots hold a keypoint, and every number of each
    # keypoint, bit for bit (an empty slot's coordinates are unspecified)
    v = np.asarray(w["top_valid"])
    return (int(g["total_count"]) == int(w["total_count"])
            and np.array_equal(g["top_valid"], v)
            and all(np.array_equal(np.asarray(g[k])[v], np.asarray(w[k])[v])
                    for k in w if k.startswith("top_")))
exact = all(same(r.results[a], ref.request_result(want[a], j))
            for j, r in enumerate(got) for a in algs)
print("RESULT", json.dumps({{
    "steps_on": {{n: sorted(d) for n, d in seen.items()}},
    "replica_device": {{n: fleet.replicas[n].device.id for n in seen}},
    "stats_device": {{n: s["device"] for n, s in stats.items()}},
    "served": sorted(served), "exact": exact}}))
"""


def test_four_thread_replicas_run_on_four_devices(tmp_path):
    """On four (virtual) devices each replica's steps run on a device of
    its own, read from the step's output arrays before they are copied
    to the host, and every answer equals the plain reference's, bit for
    bit."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES.format(root=str(root))],
        env=env, capture_output=True, text=True, timeout=600, cwd=tmp_path)
    line = [x for x in out.stdout.splitlines() if x.startswith("RESULT ")]
    assert line, out.stderr[-3000:]
    got = json.loads(line[0][len("RESULT "):])
    assert len(got["served"]) == 4 and len(got["steps_on"]) == 4
    for name, devices in got["steps_on"].items():
        assert devices == [got["replica_device"][name]], got
        assert got["stats_device"][name] == devices[0]
    assert sorted(got["replica_device"].values()) == [0, 1, 2, 3]
    assert got["exact"]


def test_thread_replicas_share_the_one_device():
    """On a host with one device every replica runs on it, as before
    replicas had devices of their own."""
    fleet = Fleet(fleet_cfg(3, max_replicas=3))
    try:
        (only,) = jax.local_devices()
        assert [r.device for r in fleet.replicas.values()] == [only] * 3
        tiles = [synthetic_scene(32, 32, 1600 + i) for i in range(6)]
        handles = [fleet.submit(t, ("harris",), scene_key=f"s-{i}")
                   for i, t in enumerate(tiles)]
        for t, h in zip(tiles, handles):
            assert_results_equal(h.result(60).results, direct(t))
        stats = fleet.stats()["replicas"]
        assert {s["device"] for s in stats.values()} == {only.id}
        for rep in fleet.replicas.values():
            tiles_, headers = rep.service.compile_cache.empty_batch(32)
            placed = rep.service.compile_cache.place(tiles_, headers)
            assert {d for a in placed for d in a.devices()} == {only}
    finally:
        fleet.close()


# ---- the router under an open loop ------------------------------------------

def test_uncollected_answers_leave_the_outstanding_table():
    """Open-loop clients never collect most answers: a request leaves
    the router's table once its replica has answered it, and its
    latency is observed all the same."""
    fleet = Fleet(fleet_cfg(2, cache_entries=64))
    try:
        hist = fleet.router._m_latency
        before = sum(hist.counts())
        tiles = [synthetic_scene(32, 32, 1700 + i) for i in range(12)]
        handles = [fleet.submit(t, ("harris",)) for t in tiles]
        deadline = time.time() + 60
        while fleet.router.outstanding and time.time() < deadline:
            time.sleep(0.01)
        assert fleet.router.outstanding == 0
        assert sum(hist.counts()) - before == len(tiles)
        assert all(h.done() for h in handles)
        # a fully cached repeat settles at submit and never enters it
        fleet.submit(tiles[0], ("harris",))
        assert fleet.router.outstanding == 0
        assert fleet.router.stats()["route_s"] > 0
    finally:
        fleet.close()


def test_kill_readmits_every_unanswered_uncollected_request():
    """A replica killed with requests queued: every request it had not
    answered is re-admitted and answered, though no caller collected it
    before the kill, and answered requests are not recomputed."""
    step_lock = threading.Lock()
    fleet = Fleet(fleet_cfg(2, max_batch=4), step_lock=step_lock)
    try:
        done_first = [fleet.submit(synthetic_scene(32, 32, 1800 + i),
                                   ("harris",), scene_key=f"early-{i}")
                      for i in range(4)]
        deadline = time.time() + 60
        while fleet.router.outstanding and time.time() < deadline:
            time.sleep(0.01)
        assert fleet.router.outstanding == 0
        tiles = [synthetic_scene(32, 32, 1900 + i) for i in range(10)]
        with step_lock:                   # all work pending or in flight
            handles = [fleet.submit(t, ("harris",), scene_key=f"late-{i}")
                       for i, t in enumerate(tiles)]
            victim = max(fleet.ready_replicas(),
                         key=lambda n: fleet.router._slots[n]
                         .service.scheduler.queue_depth)
            queued = sum(h.replica == victim for h in handles)
            assert queued > 0 and fleet.router.outstanding == len(tiles)
            fleet.kill_replica(victim)
            assert fleet.router.readmitted == queued
        deadline = time.time() + 60
        while fleet.router.outstanding and time.time() < deadline:
            time.sleep(0.01)
        assert fleet.router.outstanding == 0
        for t, h in zip(tiles, handles):
            assert h.replica != victim
            assert_results_equal(h.result(60).results, direct(t))
        assert all(h.done() for h in done_first)
    finally:
        fleet.close()
