"""Serving subsystem: buckets, LRU cache, scheduler, service parity,
determinism (DESIGN.md §8)."""
import functools
import threading
import time

import numpy as np
import pytest

import jax

from repro.configs.difet_paper import PAPER_ALGORITHMS, DifetConfig
from repro.core import engine
from repro.core.bundle import tile_scene
from repro.core.job import DifetJob
from repro.data.landsat import synthetic_scene
from repro.serve import (BatchScheduler, BucketTable, FeatureService,
                         ResultCache, ServeConfig, ServiceClosed,
                         ServiceOverloaded, config_digest, encode_tile,
                         tile_digest)

BASE = DifetConfig(tile=32, halo=8, max_keypoints_per_tile=16)
ALGS = ("harris", "shi_tomasi")


def make_service(max_batch=4, cache_entries=128, buckets=(32,),
                 max_pending=1024):
    return FeatureService(ServeConfig(
        base=BASE, buckets=buckets, max_batch=max_batch,
        max_batch_delay_s=0.005, max_pending=max_pending,
        cache_entries=cache_entries))


@pytest.fixture(scope="module")
def service():
    svc = make_service()
    yield svc
    svc.close()


# ---- algorithm normalization (shared with launch/extract.py) --------------

def test_normalize_algorithms_dedupes_preserving_order():
    assert engine.normalize_algorithms("fast, brief,fast,orb") == \
        ("fast", "brief", "orb")
    assert engine.normalize_algorithms(("harris",)) == ("harris",)


def test_normalize_algorithms_rejects_unknown_listing_choices():
    with pytest.raises(ValueError) as e:
        engine.normalize_algorithms("harris,bogus")
    msg = str(e.value)
    assert "bogus" in msg
    for name in engine.ALGORITHMS:
        assert name in msg          # the error spells out valid choices
    with pytest.raises(ValueError):
        engine.normalize_algorithms(" , ")


# ---- buckets ---------------------------------------------------------------

def test_bucket_selection():
    table = BucketTable((32, 64, 128), BASE)
    assert table.bucket_for(20, 31) == 32
    assert table.bucket_for(32, 33) == 64
    assert table.bucket_for(65, 10) == 128
    assert table.bucket_for(129, 5) is None     # oversize → scene split


def test_pad_to_bucket_matches_tile_scene_bitwise(rng):
    table = BucketTable((32, 64), BASE)
    for h, w, bucket in [(32, 32, 32), (30, 25, 32), (33, 20, 64),
                         (9, 64, 64)]:
        gray = rng.rand(h, w).astype(np.float32)
        tile, header = table.pad_to_bucket(gray, bucket)
        ref = tile_scene(gray, table.cfg_for(bucket))
        assert np.array_equal(tile, ref.tiles[0])
        assert np.array_equal(header, ref.headers[0])


def test_pad_to_bucket_sub_halo_tiles_use_multibounce_fallback(rng):
    table = BucketTable((32,), BASE)      # halo 8
    gray = rng.rand(5, 32).astype(np.float32)   # side < halo: np.pad path
    tile, header = table.pad_to_bucket(gray, 32)
    ref = tile_scene(gray, table.cfg_for(32))
    assert np.array_equal(tile, ref.tiles[0])
    assert np.array_equal(header, ref.headers[0])
    with pytest.raises(ValueError, match="too small"):
        table.pad_to_bucket(rng.rand(1, 32).astype(np.float32), 32)


# ---- result cache ----------------------------------------------------------

def _entry(i):
    return {"top_scores": np.full((4,), float(i), np.float32)}


def test_cache_lru_eviction_order():
    c = ResultCache(capacity=3)
    for k in "abc":
        c.put(k, _entry(0))
    assert c.get("a") is not None        # refresh 'a': LRU order b, c, a
    c.put("d", _entry(1))                # evicts 'b'
    assert c.get("b") is None
    assert c.get("a") is not None and c.get("c") is not None
    assert c.get("d") is not None
    assert c.evictions == 1 and len(c) == 3


def test_cache_entries_are_frozen_copies():
    c = ResultCache(capacity=2)
    src = {"x": np.ones((3,), np.float32)}
    stored = c.put("k", src)
    src["x"][0] = 99.0                   # caller mutation can't reach cache
    assert c.get("k")["x"][0] == 1.0
    with pytest.raises(ValueError):
        stored["x"][0] = 5.0             # read-only
    assert c.get("k")["x"].shape == (3,)
    zero_d = c.put("z", {"n": np.int32(7)})
    assert zero_d["n"].shape == ()       # 0-d leaves stay 0-d


def test_cache_capacity_zero_disables():
    c = ResultCache(capacity=0)
    c.put("k", _entry(0))
    assert c.get("k") is None and len(c) == 0


def test_config_digest_collision_safety():
    d1 = config_digest(BASE, use_pallas=False)
    assert config_digest(BASE, use_pallas=False) == d1
    # any config field change or backend flip must change the key
    import dataclasses
    assert config_digest(dataclasses.replace(BASE, harris_k=0.05)) != d1
    assert config_digest(dataclasses.replace(BASE, tile=64)) != d1
    assert config_digest(BASE, use_pallas=True) != d1
    c = ResultCache(capacity=8)
    c.put((tile_digest(np.zeros((4, 4))), "harris", d1), _entry(0))
    other = config_digest(dataclasses.replace(BASE, harris_k=0.05))
    assert c.get((tile_digest(np.zeros((4, 4))), "harris", other)) is None


# ---- service: parity, cache, partial hits ----------------------------------

def _direct(table, gray, algs):
    bucket = table.bucket_for(*gray.shape)
    tile, header = table.pad_to_bucket(gray, bucket)
    fn = jax.jit(functools.partial(engine.extract_features_multi,
                                   algorithms=algs, cfg=table.cfg_for(bucket)))
    return {alg: {k: np.asarray(v) for k, v in res.items()}
            for alg, res in fn(tile[None], header[None]).items()}


def assert_results_equal(a, b):
    assert set(a) == set(b)
    for alg in a:
        assert set(a[alg]) == set(b[alg])
        for k in a[alg]:
            x, y = np.asarray(a[alg][k]), np.asarray(b[alg][k])
            assert x.shape == y.shape and x.dtype == y.dtype, (alg, k)
            assert np.array_equal(x, y), (alg, k)


def test_served_parity(service):
    """Served results are bit-identical to direct engine calls, whatever
    batch the scheduler rode them in."""
    tiles = [synthetic_scene(32, 32, s) for s in range(6)]
    resps = [h.result(60) for h in
             [service.submit(t, ALGS) for t in tiles]]
    for t, r in zip(tiles, resps):
        assert_results_equal(_direct(service.table, t, ALGS), r.results)
        assert r.n_tiles == 1 and r.bucket == 32
        assert r.timing["latency_s"] >= 0.0
        assert r.timing["batch_sizes"] and r.timing["batch_sizes"][0] >= 1


@pytest.fixture(scope="module")
def service256():
    """The tile service as the benchmark's configuration runs it: bucket
    256, halo 16, 128 keypoints, batches of up to 8."""
    svc = FeatureService(ServeConfig(
        base=DifetConfig(tile=256, halo=16, max_keypoints_per_tile=128),
        buckets=(256,), max_batch=8, max_batch_delay_s=0.005))
    yield svc
    svc.close()


@pytest.mark.parametrize("alg", PAPER_ALGORITHMS)
def test_served_parity_per_algorithm(service256, alg):
    """Each algorithm served at the 256 bucket is bit-identical to a
    direct engine call on the same padded tile, for full tiles and a
    smaller one padded into the bucket, whatever batch they rode in."""
    tiles = [synthetic_scene(256, 256, seed=s, density=4.0)
             for s in range(3)] + [synthetic_scene(200, 230, seed=3)]
    handles = [service256.submit(t, (alg,)) for t in tiles]
    for t, h in zip(tiles, handles):
        r = h.result(120)
        assert r.bucket == 256
        assert_results_equal(_direct(service256.table, t, (alg,)), r.results)
        assert r.results[alg]["top_valid"].any()


def test_repeat_requests_served_from_cache(service):
    tile = synthetic_scene(32, 32, 77)
    first = service.extract(tile, ALGS, timeout=60)
    assert not first.fully_cached
    hits_before = service.cache.hits
    again = service.extract(tile, ALGS, timeout=60)
    assert again.fully_cached
    assert again.cached == {a: 1.0 for a in ALGS}
    assert service.cache.hits >= hits_before + len(ALGS)
    assert_results_equal(first.results, again.results)


def test_partial_algorithm_cache_hit(service):
    tile = synthetic_scene(32, 32, 123)
    service.extract(tile, ("harris",), timeout=60)
    r = service.extract(tile, ALGS, timeout=60)   # harris cached, shi fresh
    assert r.cached["harris"] == 1.0 and r.cached["shi_tomasi"] == 0.0
    assert_results_equal(_direct(service.table, tile, ALGS), r.results)


def test_wire_format_and_scene_id(service):
    tile = synthetic_scene(32, 32, 5)
    via_bytes = service.extract(encode_tile(tile), ("harris",), timeout=60)
    service.register_scene("granule-5", tile)
    via_id = service.extract("granule-5", ("harris",), timeout=60)
    assert_results_equal(via_bytes.results, via_id.results)
    with pytest.raises(KeyError):
        service.submit("nope", ("harris",))


def test_scene_request_splits_and_merges(service):
    """Oversize image → largest-bucket tiles, merged with the batch job's
    reduce; bit-identical to the jitted per-tile reference."""
    scene = synthetic_scene(70, 70, 9)
    cfg = service.table.cfg_for(32)
    b = tile_scene(scene, cfg)
    fn = jax.jit(functools.partial(engine.extract_request_features,
                                   algorithms=("harris",), cfg=cfg))
    per = {k: np.asarray(v)
           for k, v in fn(b.tiles, b.headers)["harris"].items()}
    want = DifetJob._merge([{k: v[i] for k, v in per.items()}
                            for i in range(len(b))])
    r = service.submit(scene, "harris").result(60)
    assert r.n_tiles == len(b) == 9
    assert_results_equal({"harris": want}, r.results)


def test_algorithm_order_canonicalized_one_program():
    """Permuted algorithm lists share one compiled program and batch
    group; the response still reports the request's order."""
    svc = make_service(max_batch=4, cache_entries=64)
    try:
        r1 = svc.extract(synthetic_scene(32, 32, 200),
                         ("shi_tomasi", "harris"), timeout=60)
        r2 = svc.extract(synthetic_scene(32, 32, 201),
                         ("harris", "shi_tomasi"), timeout=60)
        assert r1.algorithms == ("shi_tomasi", "harris")
        assert r2.algorithms == ("harris", "shi_tomasi")
        assert svc.compile_cache.keys() == [(32, ("harris", "shi_tomasi"))]
        assert_results_equal(
            _direct(svc.table, synthetic_scene(32, 32, 200),
                    ("shi_tomasi", "harris")), r1.results)
    finally:
        svc.close()


def test_warmup_compiles_each_pair_exactly_once():
    svc = make_service(max_batch=2, cache_entries=0)
    try:
        assert svc.warmup([("harris",)]) == 1
        assert svc.warmup([("harris",)]) == 1     # idempotent
        for s in range(3):
            svc.extract(synthetic_scene(32, 32, s), ("harris",), timeout=60)
        assert svc.compile_cache.programs == 1    # traffic added no programs
        assert svc.compile_cache.keys() == [(32, ("harris",))]
    finally:
        svc.close()


# ---- determinism -----------------------------------------------------------

def test_arrival_order_determinism():
    """The same request set in different arrival orders (different batch
    partitions) yields bit-identical per-request results."""
    tiles = [synthetic_scene(32, 32, 40 + s) for s in range(10)]
    orders = [list(range(10)), [9, 3, 1, 7, 5, 0, 8, 2, 6, 4]]
    outcomes = []
    for order in orders:
        svc = make_service(max_batch=4, cache_entries=0)
        try:
            handles = {i: svc.submit(tiles[i], ("harris",)) for i in order}
            outcomes.append({i: handles[i].result(60).results
                             for i in order})
        finally:
            svc.close()
    for i in range(10):
        assert_results_equal(outcomes[0][i], outcomes[1][i])


# ---- latency accounting -----------------------------------------------------

def test_open_loop_latency_not_inflated_by_drain_order():
    """Reported latency is the batch-completion stamp minus enqueue — not
    when ``result()`` got around to being called.  An open-loop client
    injects everything up front, waits out the whole run, then drains in
    submit order; the first request's latency must reflect its (first,
    fast) batch, not the drain delay."""
    import time

    svc = make_service(max_batch=1, cache_entries=0)
    try:
        svc.warmup([("harris",)])
        delay = 0.08
        orig = svc._run_batch

        def slow(bucket, algs, items):        # fixed per-batch service time
            time.sleep(delay)
            orig(bucket, algs, items)

        svc.scheduler._run_batch = slow
        # inject faster than service: all 4 submitted before batch 1 ends
        tiles = [synthetic_scene(32, 32, 400 + s) for s in range(4)]
        submit_t0 = time.perf_counter()
        handles = [svc.submit(t, ("harris",)) for t in tiles]
        while not all(h.done() for h in handles):
            time.sleep(0.01)
        time.sleep(0.3)                       # the drain wait under test
        lats = [h.result(60).timing["latency_s"] for h in handles]
        drain_wall = time.perf_counter() - submit_t0
        # every request completed long before result() was called...
        assert drain_wall > 0.3
        # ...and the first request's latency is ~one service time, far
        # below the drain wall (pre-fix it equaled drain_wall)
        assert lats[0] < 0.3 < drain_wall
        # later queue positions waited behind earlier batches
        assert lats[-1] >= lats[0]
        for r in [h.result(60) for h in handles]:
            assert r.timing["completed_at"] >= r.timing["enqueued_at"]
    finally:
        svc.close()


def test_fully_cached_response_reports_zero_queue_latency():
    """A request served entirely from the result cache never touched the
    device; its completion stamp is its enqueue stamp."""
    svc = make_service(max_batch=2, cache_entries=64)
    try:
        tile = synthetic_scene(32, 32, 900)
        svc.extract(tile, ("harris",), timeout=60)
        r = svc.extract(tile, ("harris",), timeout=60)
        assert r.fully_cached
        assert r.timing["completed_at"] == r.timing["enqueued_at"]
        assert r.timing["latency_s"] == 0.0
    finally:
        svc.close()


def test_scheduler_wait_is_enqueue_to_batch_formation():
    """``wait_s`` and the queue histogram observe enqueue → batch
    formation: a lone item waits out the batch delay, and a slow device
    step after formation adds nothing to either."""
    delay, step = 0.05, 0.3

    def slow(bucket, algs, items):
        time.sleep(step)
        for it in items:
            it.resolve(None)

    sched = BatchScheduler(slow, max_batch=4, max_batch_delay_s=delay)
    try:
        fut = sched.submit(np.zeros((32, 32)), np.zeros(6), 32, ("harris",))
        fut.result(10)
        s = sched.stats()
        assert s["items"] == 1
        assert delay <= s["wait_s"] < step
        assert sched.queue_hist.count == 1
        assert sched.queue_hist.quantile(0.5) < step
    finally:
        sched.stop(10)


def test_completed_at_is_stamped_after_slicing_and_caching(monkeypatch):
    """``timing["completed_at"]`` is the moment the tile's answer was
    ready: after its results were sliced and cached, not at the end of
    the device step."""
    svc = make_service(max_batch=4, cache_entries=64)
    puts = []
    real_put = svc.cache.put

    def put(key, value):
        out = real_put(key, value)
        puts.append(time.time())
        return out
    monkeypatch.setattr(svc.cache, "put", put)
    try:
        svc.warmup([("harris",)])
        r = svc.extract(synthetic_scene(32, 32, 911), ("harris",),
                        timeout=60)
        assert len(puts) == 1
        assert r.timing["completed_at"] >= puts[0]
    finally:
        svc.close()


# ---- scheduler: backpressure + coalescing ----------------------------------

def test_scheduler_backpressure():
    release = threading.Event()
    done = []

    def blocking_runner(bucket, algs, items):
        release.wait(30)
        for it in items:
            it.future.set_result(("ok", it.batch_size))
            done.append(it.seq)

    sched = BatchScheduler(blocking_runner, max_batch=1,
                           max_batch_delay_s=0.0, max_pending=2)
    tile = np.zeros((4, 4), np.float32)
    header = np.zeros((6,), np.int32)
    futures, rejected = [], 0
    for _ in range(6):
        try:
            futures.append(sched.submit(tile, header, 4, ("harris",)))
        except ServiceOverloaded:
            rejected += 1
    assert rejected >= 1                      # queue bounded, load shed
    assert sched.stats()["rejected"] == rejected
    release.set()
    for f in futures:
        assert f.result(30)[0] == "ok"        # accepted work still completes
    sched.stop(10)


def test_concurrent_identical_requests_coalesce():
    """Two in-flight requests for the same (tile, algorithms) share one
    device computation."""
    svc = make_service(max_batch=4, cache_entries=128)
    try:
        svc.warmup([("harris",)])
        tile = synthetic_scene(32, 32, 314)
        h1 = svc.submit(tile, ("harris",))
        h2 = svc.submit(tile, ("harris",))
        r1, r2 = h1.result(60), h2.result(60)
        assert_results_equal(r1.results, r2.results)
        assert svc.scheduler.items == 1       # one WorkItem served both
    finally:
        svc.close()


def test_identical_tiles_at_different_positions_never_alias():
    """Results carry scene-global coordinates (ys = ty·tile + local), so
    pixel-identical tiles at different grid positions have different
    correct outputs: the cache/coalescing key must fold the header's
    position, or the second position is served the first one's
    coordinates."""
    svc = make_service(cache_entries=128)
    try:
        svc.warmup([("harris",)])
        gray = synthetic_scene(32, 32, seed=99)
        tile, header0 = svc.table.pad_to_bucket(gray, 32)
        header1 = header0.copy()
        header1[1], header1[2] = 2, 3          # same pixels, grid (2, 3)
        cfgd = svc._cfg_digest(32)

        def run(header):
            part = svc._submit_tile(tile, header, 32, ("harris",), cfgd,
                                    block=True)
            res = dict(part.cached)
            if part.future is not None:
                computed, _, _ = part.future.result(60)
                res.update(computed)
            return res["harris"]

        r0, r1 = run(header0), run(header1)
        valid = np.asarray(r0["top_valid"]).astype(bool)
        assert valid.any()
        t = svc.table.cfg_for(32).tile
        # position must be baked into the coordinates, not aliased away
        np.testing.assert_array_equal(
            np.asarray(r1["top_ys"])[valid],
            np.asarray(r0["top_ys"])[valid] + 2 * t)
        np.testing.assert_array_equal(
            np.asarray(r1["top_xs"])[valid],
            np.asarray(r0["top_xs"])[valid] + 3 * t)
    finally:
        svc.close()


# ---- shutdown + burst-overflow regressions (fleet PR satellites) -----------

def test_stop_wakes_blocked_submitters():
    """A submitter parked on backpressure must be woken by stop() and get
    a clean ServiceClosed — not hang on the condition variable (the
    busy-wait used to re-check only queue room, never closure)."""
    release = threading.Event()

    def runner(bucket, algs, items):
        release.wait(30)
        for it in items:
            it.future.set_result("ok")

    sched = BatchScheduler(runner, max_batch=1, max_batch_delay_s=0.0,
                           max_pending=1)
    tile = np.zeros((4, 4), np.float32)
    header = np.zeros((6,), np.int32)
    f1 = sched.submit(tile, header, 4, ("harris",))
    deadline = time.monotonic() + 10
    while sched.queue_depth and time.monotonic() < deadline:
        time.sleep(0.001)                 # runner took f1 (blocked in step)
    f2 = sched.submit(tile, header, 4, ("harris",))   # queue now full
    woke = []

    def blocked_submitter():
        try:
            sched.submit(tile, header, 4, ("harris",), block=True,
                         timeout=30)
        except ServiceClosed as e:
            woke.append(e)

    t = threading.Thread(target=blocked_submitter)
    t.start()
    time.sleep(0.1)                       # let it park on the cv
    sched.stop(timeout=0.1)               # runner still blocked: just flag
    t.join(5)
    assert not t.is_alive(), "blocked submitter hung across stop()"
    assert len(woke) == 1                 # clean typed wake-up
    with pytest.raises(ServiceClosed):
        sched.submit(tile, header, 4, ("harris",))    # post-stop submit
    release.set()
    assert f1.result(30) == "ok"          # accepted work still completes
    assert f2.result(30) == "ok"
    sched.stop(10)


def test_burst_overflow_sheds_under_concurrent_submitters():
    """A synchronized burst from many client threads against a tiny
    pending bound: overflow is shed (counted per service), every accepted
    request completes, and nothing is double-counted."""
    base = DifetConfig(tile=32, halo=8, max_keypoints_per_tile=16)
    step_lock = threading.Lock()
    svc = FeatureService(ServeConfig(
        base=base, buckets=(32,), max_batch=4, max_batch_delay_s=0.001,
        max_pending=8, cache_entries=0), step_lock=step_lock)
    try:
        svc.warmup([("harris",)])
        tiles = [synthetic_scene(32, 32, 500 + i) for i in range(48)]
        handles, sheds, lock = [], [], threading.Lock()

        def client(chunk):
            for tile in chunk:
                try:
                    h = svc.submit(tile, ("harris",))
                except ServiceOverloaded:
                    with lock:
                        sheds.append(1)
                else:
                    with lock:
                        handles.append(h)

        with step_lock:                   # device stalled: queue must fill
            threads = [threading.Thread(target=client,
                                        args=(tiles[i::8],))
                       for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        assert len(sheds) >= 1            # the burst overflowed the bound
        assert len(handles) + len(sheds) == len(tiles)
        assert svc.shed == len(sheds)
        assert svc.requests == len(handles)
        for h in handles:                 # accepted work all completes
            r = h.result(60)
            assert int(r.results["harris"]["total_count"]) >= 0
    finally:
        svc.close()


def test_service_stats_flat_snapshot():
    """The per-replica counters the fleet router aggregates: flat keys,
    cheap to poll, consistent with the traffic just served."""
    svc = make_service(max_batch=4, cache_entries=64)
    try:
        svc.warmup([("harris",)])
        tile = synthetic_scene(32, 32, 907)
        svc.submit(tile, ("harris",), block=True).result(60)
        svc.submit(tile, ("harris",), block=True).result(60)   # cache hit
        s = svc.stats()
        for key in ("name", "submitted", "shed", "cache_hits",
                    "cache_misses", "queue_depth", "batches",
                    "batch_occupancy", "p50_queue_ms", "p99_queue_ms",
                    "busy_s", "steps"):
            assert key in s, key
        assert s["submitted"] == 2 and s["shed"] == 0
        assert s["cache_hits"] >= 1 and s["cache_misses"] >= 1
        assert s["steps"] >= 1 and s["busy_s"] > 0.0
        assert 0.0 < s["batch_occupancy"] <= 1.0
        assert s["p99_queue_ms"] >= s["p50_queue_ms"] >= 0.0
    finally:
        svc.close()


def test_work_item_settlement_is_idempotent_first_wins():
    """Regression: ``stop()``/``kill()`` racing an in-flight
    ``_run_batch`` used to double-resolve a future through ad-hoc
    ``done()``-then-set guards.  `WorkItem.resolve`/`WorkItem.fail` are
    the only settlement paths now: exactly one caller wins, losers are
    no-ops, and many racing threads agree on the outcome."""
    from concurrent.futures import Future

    from repro.serve import ReplicaDied, WorkItem

    def item():
        return WorkItem(seq=0, tile=np.zeros((32, 32), np.float32),
                        header=np.zeros(6, np.int32), bucket=32,
                        algorithms=("harris",), digest="d",
                        cfg_digest="c", future=Future())

    # sequential: the second settlement (either kind) is a no-op
    it = item()
    assert it.resolve("first") and not it.resolve("second")
    assert not it.fail(ReplicaDied("late kill"))
    assert it.future.result(0) == "first"
    it = item()
    assert it.fail(ReplicaDied("kill won")) and not it.resolve("late batch")
    with pytest.raises(ReplicaDied):
        it.future.result(0)

    # concurrent: N resolvers vs N failers on one item — exactly one
    # winner, the future holds exactly that side's outcome
    for trial in range(20):
        it = item()
        start = threading.Barrier(8)
        wins = []

        def run(op, tag):
            start.wait()
            if op():
                wins.append(tag)
        threads = (
            [threading.Thread(target=run,
                              args=((lambda i=i: it.resolve(f"r{i}")),
                                    "resolve")) for i in range(4)] +
            [threading.Thread(target=run,
                              args=((lambda i=i: it.fail(
                                  ReplicaDied(f"f{i}"))),
                                    "fail")) for i in range(4)])
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        assert len(wins) == 1, wins
        if wins[0] == "resolve":
            assert str(it.future.result(0)).startswith("r")
        else:
            with pytest.raises(ReplicaDied):
                it.future.result(0)


def test_scheduler_kill_vs_completion_race_single_outcome():
    """Scheduler-level settle race: ``kill()`` fired while a batch is
    mid-flight.  Whichever side wins, every accepted future settles
    exactly once — a result bit-identical to the direct path, or
    ``ReplicaDied`` — and never hangs or raises InvalidStateError."""
    release = threading.Event()

    def slow_runner(bucket, algorithms, batch):
        release.wait(10)
        for it in batch:
            it.resolve({"ok": it.seq})

    sched = BatchScheduler(slow_runner, max_batch=4,
                           max_batch_delay_s=0.001, max_pending=64,
                           name="settle-race")
    futs = [sched.submit(np.zeros((32, 32), np.float32), np.zeros(6),
                         32, ("harris",)) for _ in range(4)]
    deadline = time.monotonic() + 5.0
    while not sched._active and time.monotonic() < deadline:
        time.sleep(0.002)                 # batch now on-device
    killer = threading.Thread(target=sched.kill)
    killer.start()
    release.set()                         # completion races the kill
    killer.join(10)
    outcomes = []
    for f in futs:
        try:
            outcomes.append(("ok", f.result(10)))
        except Exception as e:  # noqa: BLE001
            outcomes.append(("died", type(e).__name__))
    assert len(outcomes) == 4             # every future settled, none hung
    for kind, val in outcomes:
        assert kind in ("ok", "died")
        if kind == "died":
            assert val == "ReplicaDied"
