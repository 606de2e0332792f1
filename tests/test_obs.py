"""Observability layer (`repro/obs/`): bounded-memory histograms and
the registry, span tracing + flight recorder, exporters/validator, the
kernel profiler, dispatch-cache provenance, and the end-to-end gates —
trace-id continuity across a chaos kill, and traced-run bit-parity."""
import json
import os
import threading
from pathlib import Path

import numpy as np
import pytest

from repro.obs import export as obs_export
from repro.obs import metrics as obs_metrics
from repro.obs import profile as obs_profile
from repro.obs import trace as obs_trace
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import FlightRecorder, NoopRecorder, Span


@pytest.fixture
def flight(tmp_path):
    """Install a FlightRecorder (tracing ON) for the test, restore the
    process default (noop) afterwards.  ``DIFET_CHAOS_DUMP_DIR``
    redirects crash-dump artifacts to a stable path — CI sets it so a
    failing chaos test leaves its Chrome trace behind for upload."""
    dump_dir = os.environ.get("DIFET_CHAOS_DUMP_DIR", str(tmp_path))
    Path(dump_dir).mkdir(parents=True, exist_ok=True)
    rec = FlightRecorder(capacity=4096, dump_dir=dump_dir)
    prev = obs_trace.set_recorder(rec)
    yield rec
    obs_trace.set_recorder(prev)


@pytest.fixture
def fresh_registry():
    """Swap in an empty registry so counter assertions see only this
    test's traffic; restore the process default afterwards."""
    reg = MetricsRegistry()
    prev = obs_metrics.set_registry(reg)
    yield reg
    obs_metrics.set_registry(prev)


# ---- metrics primitives ----------------------------------------------------

def test_histogram_bounded_memory_under_load():
    """The regression that retires the unbounded latency lists: 100k
    observations grow the histogram by ZERO bytes of per-observation
    state — bucket count and attribute set stay constant."""
    h = Histogram("t.load")
    n_buckets = len(h._counts)
    rng = np.random.RandomState(0)
    h.observe_many(rng.lognormal(-6, 2, size=100_000).tolist())
    assert len(h._counts) == n_buckets        # no per-observation growth
    assert h.count == 100_000
    assert sum(h._counts) == 100_000
    assert set(vars(h)) == set(vars(Histogram("t.fresh")))  # no new attrs


def test_histogram_quantiles_interpolated_accuracy():
    """Interpolated quantiles land within one bucket width (factor 1.25
    edges => <=25% relative error) of numpy's exact percentiles."""
    rng = np.random.RandomState(7)
    vals = rng.lognormal(mean=-5.0, sigma=1.0, size=20_000)
    h = Histogram("t.acc")
    h.observe_many(vals.tolist())
    for q in (0.50, 0.95, 0.99):
        exact = float(np.percentile(vals, q * 100))
        est = h.quantile(q)
        assert exact / 1.3 <= est <= exact * 1.3, (q, est, exact)
    assert h.quantile(0.0) >= 0.0
    assert h.quantile(1.0) <= h.max * (1 + 1e-9)
    # monotone in q — the scheduler stats() p99 >= p50 contract
    qs = [h.quantile(q) for q in (0.1, 0.5, 0.9, 0.95, 0.99)]
    assert all(b >= a for a, b in zip(qs, qs[1:]))
    assert h.mean == pytest.approx(vals.mean(), rel=1e-6)


def test_histogram_edge_cases():
    h = Histogram("t.edge")
    assert h.quantile(0.5) == 0.0             # empty
    snap = h.snapshot()
    assert snap["count"] == 0 and snap["p99"] == 0.0
    h.observe(0.001)                          # single observation
    assert h.quantile(0.5) == pytest.approx(0.001, rel=0.3)
    h.observe(1e9)                            # overflow bucket
    assert h.count == 2 and h.max == 1e9
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        Histogram("t.bad", bounds=(2.0, 1.0))


def test_registry_create_on_first_use_and_type_guard():
    reg = MetricsRegistry()
    c = reg.counter("difet.test.n")
    assert reg.counter("difet.test.n") is c   # shared instance
    c.inc()
    c.inc(2.5)
    reg.gauge("difet.test.depth").set(7)
    reg.histogram("difet.test.lat_s").observe(0.25)
    with pytest.raises(TypeError):
        reg.histogram("difet.test.n")         # name is a Counter
    snap = reg.snapshot()
    assert snap["difet.test.n"] == 3.5
    assert snap["difet.test.depth"] == 7.0
    assert snap["difet.test.lat_s"]["count"] == 1
    assert reg.names() == sorted(snap)
    reg.reset()
    assert reg.names() == []


def test_counter_gauge_thread_safety():
    c, g = Counter("c"), Gauge("g")

    def work():
        for _ in range(1000):
            c.inc()
            g.set(1.0)

    ts = [threading.Thread(target=work) for _ in range(8)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert c.value == 8000.0
    assert g.value == 1.0


# ---- tracing ---------------------------------------------------------------

def test_noop_default_records_nothing():
    prev = obs_trace.set_recorder(NoopRecorder())
    try:
        assert not obs_trace.enabled()
        assert obs_trace.emit_span("x", "router", 0.0, 1.0) is None
        with obs_trace.span("y", "cache"):
            pass
        assert obs_trace.get_recorder().spans() == []
    finally:
        obs_trace.set_recorder(prev)


def test_flight_recorder_ring_bound_and_dump_dedupe(tmp_path):
    rec = FlightRecorder(capacity=10, dump_dir=str(tmp_path))
    prev = obs_trace.set_recorder(rec)
    try:
        for i in range(25):
            obs_trace.emit_span(f"s{i}", "router", float(i), float(i) + 0.5)
        spans = rec.spans()
        assert len(spans) == 10               # ring bound holds
        assert spans[0].name == "s15"         # oldest fell off the back
        assert rec.emitted == 25
        p1 = rec.dump_on("crash")
        p2 = rec.dump_on("crash")             # deduped: one artifact
        assert p1 is not None and p2 is None
        doc = json.load(open(p1))
        assert doc["metadata"]["dump_reason"] == "crash"
        assert len(doc["traceEvents"]) == 10
        assert rec.dump_on("shed-other") is not None    # new reason dumps
        assert set(rec.dumps) == {"crash", "shed-other"}
    finally:
        obs_trace.set_recorder(prev)


def test_span_ids_ambient_trace_and_attrs(flight):
    tid = obs_trace.new_trace_id()
    assert obs_trace.current_trace_id() == ""
    with obs_trace.use_trace(tid):
        assert obs_trace.current_trace_id() == tid
        with obs_trace.span("disk_get", "cache", bytes=128):
            pass
    assert obs_trace.current_trace_id() == ""       # restored
    [s] = flight.spans()
    assert s.trace_id == tid                        # ambient id captured
    assert s.layer == "cache" and dict(s.attrs)["bytes"] == 128
    assert s.t1 >= s.t0 and s.duration_s >= 0.0
    sid = obs_trace.emit_span("child", "cache", 0.0, 1.0,
                              trace_id=tid, parent_id=s.span_id)
    child = flight.spans()[-1]
    assert child.parent_id == s.span_id and child.span_id == sid


def test_span_writes_a_profiler_traceme_and_yields_its_id(flight, tmp_path):
    """Every span() is also a ``difet.<layer>.<name>`` TraceMe in an active
    ``jax.profiler`` session (on the device trace's clock); the recorded
    span's id is what the block gets, so children can name their parent.
    emit_span() is recorder-only: a TraceMe cannot be back-dated."""
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.span("deliver", "batch") as sid:
            with obs_trace.span("disk_put", "cache"):
                pass
        obs_trace.emit_span("queue_wait", "scheduler", 0.0, 1.0)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    names = [e.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("difet.")]
    assert sorted(names) == ["difet.batch.deliver", "difet.cache.disk_put"]
    recorded = {s.name: s for s in flight.spans()}
    assert sid == recorded["deliver"].span_id
    obs_trace.set_recorder(NoopRecorder())
    with obs_trace.span("deliver", "batch") as off:
        assert off is None                          # nothing recorded


# ---- exporters + validator -------------------------------------------------

def _mk_span(name, layer, t0, t1, tid="t1"):
    return Span(name=name, layer=layer, trace_id=tid, span_id="s1",
                parent_id="", t0=t0, t1=t1, thread="main")


def test_chrome_export_schema_and_validator():
    spans = [_mk_span("queue_wait", "scheduler", 2.0, 3.0),
             _mk_span("admit", "router", 1.0, 1.5),
             _mk_span("device_step", "kernel", 3.0, 3.2)]
    doc = obs_export.spans_to_chrome(spans, metadata={"run": "test"})
    evs = doc["traceEvents"]
    assert [e["name"] for e in evs] == ["admit", "queue_wait", "device_step"]
    assert evs[0]["ts"] == 0.0                      # rebased to trace start
    assert evs[0]["dur"] == pytest.approx(0.5e6)    # microseconds
    assert evs[1]["cat"] == "scheduler"
    assert obs_export.validate_chrome_trace(
        doc, required_layers=("router", "scheduler", "kernel")) == []
    # validator catches: missing layer, open span, wrong phase, bad order
    assert obs_export.validate_chrome_trace(doc, required_layers=("cache",))
    bad = {"traceEvents": [dict(evs[0], dur=-1.0)]}
    assert any("unclosed" in p
               for p in obs_export.validate_chrome_trace(bad))
    bad = {"traceEvents": [dict(evs[0], ph="B")]}
    assert any("ph" in p for p in obs_export.validate_chrome_trace(bad))
    bad = {"traceEvents": [dict(evs[1], ts=5.0), dict(evs[0], ts=1.0)]}
    assert any("monotonic" in p
               for p in obs_export.validate_chrome_trace(bad))
    assert obs_export.validate_chrome_trace({}) == \
        ["traceEvents missing or empty"]


def test_latency_breakdown_and_report(fresh_registry):
    reg = fresh_registry
    reg.histogram("difet.scheduler.queue_s").observe_many([0.001, 0.002])
    reg.histogram("difet.kernel.step_s").observe(0.005)
    reg.counter("difet.router.admitted").inc(3)
    payload = obs_export.metrics_payload(reg)
    rows = obs_export.latency_breakdown(payload["metrics"])
    assert [r["stage"] for r in rows] == ["queue", "kernel"]
    assert rows[0]["count"] == 2
    report = obs_export.render_report(payload)
    assert "queue" in report and "difet.router.admitted" in report


# ---- kernel profiler -------------------------------------------------------

def test_profiler_disabled_by_default_and_rows_when_on():
    assert not obs_profile.profiler().enabled
    obs_profile.record_call("match:l2:jnp_full:q64k1024d32", 1.0)
    assert obs_profile.profiler().snapshot() == {}       # noop discarded
    prev = obs_profile.set_profiler(obs_profile.KernelProfiler())
    try:
        with obs_profile.profile_call("k1"):
            pass
        obs_profile.record_call("k1", 0.5)
        obs_profile.record_compile("k1", 2.0)
        rows = obs_profile.profiler().snapshot()
        assert rows["k1"]["calls"] == 2
        assert rows["k1"]["wall_s"] >= 0.5
        assert rows["k1"]["compiles"] == 1
        assert rows["k1"]["compile_s"] == 2.0
    finally:
        obs_profile.set_profiler(prev)
    with obs_profile.capture(None) as on:
        assert on is False                               # gated, optional


def test_match_best2_profiles_by_dispatch_bucket(tmp_path, monkeypatch):
    from repro.kernels import dispatch, ops
    monkeypatch.setenv(dispatch.CACHE_ENV, str(tmp_path / "d.json"))
    dispatch.clear_memory_cache()
    rng = np.random.RandomState(0)
    q = rng.randn(16, 32).astype(np.float32)
    db = rng.randn(200, 32).astype(np.float32)
    base = [np.asarray(x) for x in ops.match_best2(q, db, metric="l2")]
    prev = obs_profile.set_profiler(obs_profile.KernelProfiler())
    try:
        out = ops.match_best2(q, db, metric="l2")
        rows = obs_profile.profiler().snapshot()
        match_rows = [k for k in rows if k.startswith("match:l2:")]
        assert match_rows, rows
        assert "q16k256d32" in match_rows[0]     # pow2 dispatch bucket key
    finally:
        obs_profile.set_profiler(prev)
        dispatch.clear_memory_cache()
    for a, b in zip(base, out):                  # profiling never forks bits
        np.testing.assert_array_equal(a, np.asarray(b))


def test_dispatch_cache_provenance_and_explain(tmp_path, monkeypatch):
    """Satellite: every measured verdict persists WHY it won — candidate
    set, per-candidate timings, probe shape — and explain() decodes it."""
    from repro.kernels import dispatch
    path = str(tmp_path / "dispatch.json")
    monkeypatch.setenv(dispatch.CACHE_ENV, path)
    dispatch.clear_memory_cache()
    try:
        p = dispatch.choose_path("l2", 32, 512, 16)
        entry = json.load(open(path))
        [(key, val)] = entry.items()
        assert val["path"] == p
        assert val["metric"] == "l2" and val["backend"] == "cpu"
        assert val["bucket"] == [32, 512, 16]
        assert sorted(val["candidates"]) == sorted(val["us"])
        assert all(us > 0 for us in val["us"].values())
        rows = dispatch.explain()
        assert rows[key]["path"] == p
        assert rows[key]["margin"] >= 1.0        # winner beat the runner-up
    finally:
        dispatch.clear_memory_cache()


# ---- serving integration ---------------------------------------------------

def _serve_cfg():
    from repro.configs.difet_paper import DifetConfig
    from repro.serve import ServeConfig
    return ServeConfig(base=DifetConfig(tile=32, halo=8,
                                        max_keypoints_per_tile=16),
                       buckets=(32,), max_batch=4)


def test_scheduler_quantiles_bounded_not_listy():
    """Satellite (a): scheduler stats() quantiles come from the bounded
    histogram — no per-request list anywhere on the instance — and the
    p99 >= p50 >= 0 contract holds under traffic."""
    from repro.data.landsat import synthetic_scene
    from repro.serve import FeatureService
    svc = FeatureService(_serve_cfg())
    try:
        svc.warmup([("harris",)])
        n_buckets = len(svc.scheduler.queue_hist._counts)
        for i in range(32):
            svc.extract(synthetic_scene(32, 32, i), ("harris",), timeout=60)
        s = svc.scheduler.stats()
        assert s["items"] == 32
        assert s["p99_queue_ms"] >= s["p50_queue_ms"] >= 0.0
        assert len(svc.scheduler.queue_hist._counts) == n_buckets
        assert svc.scheduler.queue_hist.count == 32
        # nothing on the scheduler accumulates per-request entries
        for v in vars(svc.scheduler).values():
            if isinstance(v, (list, tuple)) and len(v) > 20:
                pytest.fail(f"unbounded per-request container: {v[:3]}...")
    finally:
        svc.close()


def test_untraced_service_emits_no_spans():
    from repro.data.landsat import synthetic_scene
    from repro.serve import FeatureService
    assert not obs_trace.enabled()               # process default is noop
    svc = FeatureService(_serve_cfg())
    try:
        svc.warmup([("harris",)])
        svc.extract(synthetic_scene(32, 32, 1), ("harris",), timeout=60)
        assert obs_trace.get_recorder().spans() == []
    finally:
        svc.close()


def test_traced_run_bit_identical_to_untraced(flight):
    """Instrumentation only observes: the traced service returns the
    exact bits of an untraced one on the same tile."""
    from repro.data.landsat import synthetic_scene
    from repro.serve import FeatureService
    from test_fleet import assert_results_equal

    tile = synthetic_scene(32, 32, 42)

    def run():
        svc = FeatureService(_serve_cfg())
        try:
            svc.warmup([("harris",)])
            return {a: {k: np.asarray(v) for k, v in r.items()}
                    for a, r in svc.extract(tile, ("harris",),
                                            timeout=60).results.items()}
        finally:
            svc.close()

    traced = run()
    obs_trace.set_recorder(NoopRecorder())
    untraced = run()
    assert_results_equal(traced, untraced)
    assert len(flight.spans()) > 0               # the traced run DID record


def test_traced_request_spans_every_layer(flight, tmp_path):
    """One routed request produces spans from router + scheduler + batch
    + kernel, all sharing the trace id minted at admission; a disk-tier
    service adds cache spans under the same id."""
    from repro.data.landsat import synthetic_scene
    from repro.serve import Router, RouterConfig, FeatureService
    from repro.serve.api import ServeConfig
    import dataclasses as dc

    cfg = dc.replace(_serve_cfg(), cache_dir=str(tmp_path / "tier"))
    svc = FeatureService(cfg, name="rep-1")
    router = Router(RouterConfig())
    try:
        svc.warmup([("harris",)])
        router.add_replica("rep-1", svc)
        h = router.submit(synthetic_scene(32, 32, 9), ("harris",))
        h.result(60)
        spans = flight.spans()
        admits = [s for s in spans if s.name == "admit"]
        assert len(admits) == 1
        tid = admits[0].trace_id
        assert tid                                # minted at admission
        layers_for_tid = {s.layer for s in spans if s.trace_id == tid}
        assert {"router", "scheduler", "batch",
                "cache"} <= layers_for_tid, layers_for_tid
        assert any(s.layer == "kernel" for s in spans)  # batch-scoped
        wait = [s for s in spans
                if s.name == "queue_wait" and s.trace_id == tid]
        assert wait and dict(wait[0].attrs)["replica"] == "rep-1"
    finally:
        router.close()
        svc.close()


def test_trace_id_survives_chaos_readmit(flight):
    """Satellite (c): kill a replica holding queued + in-flight work; the
    re-admitted request's spans on the survivor carry the ORIGINAL trace
    id, linked by a router `readmit` span naming old and new replica."""
    from repro.data.landsat import synthetic_scene
    from repro.serve import Fleet
    from test_fleet import assert_results_equal, direct, fleet_cfg

    step_lock = threading.Lock()
    fleet = Fleet(fleet_cfg(2, max_batch=4), step_lock=step_lock)
    try:
        tiles = [synthetic_scene(32, 32, 900 + i) for i in range(8)]
        with step_lock:                    # hold every batch in flight
            handles = [fleet.submit(t, ("harris",), scene_key=f"sc-{i}")
                       for i, t in enumerate(tiles)]
            victim = max(fleet.ready_replicas(),
                         key=lambda n: fleet.router._slots[n]
                         .service.scheduler.queue_depth)
            fleet.kill_replica(victim)     # re-admission happens in here
        results = [h.result(60) for h in handles]
        for t, r in zip(tiles, results):
            assert_results_equal(r.results, direct(t))

        spans = flight.spans()
        admit_tids = {s.trace_id for s in spans if s.name == "admit"}
        readmits = [s for s in spans if s.name == "readmit"]
        assert readmits                    # the kill produced re-admissions
        for s in readmits:
            attrs = dict(s.attrs)
            assert s.trace_id in admit_tids          # SAME trace id
            assert attrs["old_replica"] == victim
            assert attrs["new_replica"] != victim
        # the recompute on the survivor is tagged with the original id:
        # a queue_wait span with a readmitted trace id, recorded AFTER
        # the kill, living on the surviving replica
        readmit_tids = {s.trace_id for s in readmits}
        t_kill = min(s.t0 for s in readmits)
        recompute = [s for s in spans
                     if s.name == "queue_wait" and s.t1 >= t_kill
                     and s.trace_id in readmit_tids
                     and dict(s.attrs).get("replica") != victim]
        assert recompute, "no recompute spans carry the original trace id"
        # the dead replica's orphaned work was marked
        assert any(s.name == "killed" and s.layer == "scheduler"
                   for s in spans)
        # flight recorder dumped the replica_died artifact exactly once
        assert "replica_died" in flight.dumps
    finally:
        fleet.close()


def test_shed_counters_in_registry(fresh_registry):
    from repro.serve import Router, RouterConfig, Shed
    router = Router(RouterConfig())
    with pytest.raises(Shed):
        router.submit(np.zeros((32, 32), np.float32), ("harris",))
    snap = fresh_registry.snapshot()
    assert snap.get("difet.router.shed.no_ready_replica") == 1.0


def test_trace_id_survives_process_kill_readmit(flight, tmp_path):
    """The process-fleet variant of trace-id continuity: a replica
    *process* is SIGKILLed holding outstanding work, the death is
    discovered via the stale lease, and the router's `readmit` spans
    carry the ORIGINAL admission-minted trace id — the request's
    identity survives a real cross-process crash."""
    from chaos import ChaosPlan, clear_plan, wait_until, write_plan
    from repro.data.landsat import synthetic_scene
    from repro.serve import Fleet
    from repro.serve.fleet import DEAD
    from test_proc_fleet import proc_fleet_cfg

    fleet = Fleet(proc_fleet_cfg(tmp_path, 2))
    try:
        for name in fleet.ready_replicas():   # keep work outstanding
            write_plan(fleet.transport_dir / name,
                       ChaosPlan(hold_responses_s=30.0))
        tiles = [synthetic_scene(32, 32, 950 + i) for i in range(6)]
        handles = [fleet.submit(t, ("harris",), scene_key=f"pk-{i}")
                   for i, t in enumerate(tiles)]
        victim = next(iter(fleet.router._outstanding.values())).replica
        fleet.sigkill_replica(victim)
        for name in fleet.ready_replicas():
            clear_plan(fleet.transport_dir / name)

        def detected():
            fleet.maintenance_tick()
            return fleet.replicas[victim].state == DEAD
        wait_until(detected, 20, desc="stale-lease detection")
        for h in handles:                     # all accepted work completes
            h.result(90)

        spans = flight.spans()
        admit_tids = {s.trace_id for s in spans if s.name == "admit"}
        readmits = [s for s in spans if s.name == "readmit"]
        assert readmits                       # the SIGKILL forced re-admission
        for s in readmits:
            attrs = dict(s.attrs)
            assert s.trace_id in admit_tids   # original admission-minted id
            assert attrs["old_replica"] == victim
            assert attrs["new_replica"] != victim
    finally:
        fleet.close()
