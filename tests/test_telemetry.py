"""Fleet telemetry plane: cross-process metrics shipping
(`repro/obs/ship.py`), parent-side aggregation (`repro/obs/agg.py`),
SLO burn-rate monitoring (`repro/obs/slo.py`), the Prometheus exporter,
and the torn-snapshot transport fix.

The histogram-mergeability property — K workers' shipped bucket deltas
merged parent-side are *indistinguishable* from one histogram that
observed the union stream — runs both as a seeded plain test (always)
and as a hypothesis property test (skipped when hypothesis is absent;
the container does not ship it).
"""
import json
import math
import os
import random
import time

import numpy as np
import pytest

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.agg import TelemetryAggregator, fleet_metric_name
from repro.obs.export import (render_prometheus, spans_to_chrome,
                              validate_chrome_trace)
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.ship import TelemetryShipper, span_from_wire, span_to_wire
from repro.obs.slo import BurnRateMonitor, SloPolicy
from repro.obs.trace import FlightRecorder, Span
from repro.serve.transport import WorkerMailbox, read_message, read_snapshot


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                         # pragma: no cover
    HAVE_HYPOTHESIS = False

    def given(*a, **k):                                     # noqa: D103
        return lambda f: pytest.mark.skip("hypothesis not installed")(f)

    def settings(*a, **k):                                  # noqa: D103
        return lambda f: f

    class st:                                               # noqa: D101
        @staticmethod
        def lists(*a, **k):
            return None

        @staticmethod
        def floats(*a, **k):
            return None

        @staticmethod
        def integers(*a, **k):
            return None


@pytest.fixture
def fresh_registry():
    reg = MetricsRegistry()
    prev = obs_metrics.set_registry(reg)
    yield reg
    obs_metrics.set_registry(prev)


@pytest.fixture
def flight(tmp_path):
    (tmp_path / "dumps").mkdir(exist_ok=True)
    rec = FlightRecorder(capacity=4096, dump_dir=str(tmp_path / "dumps"))
    prev = obs_trace.set_recorder(rec)
    yield rec
    obs_trace.set_recorder(prev)


# ---- torn-snapshot transport regression ------------------------------------

def test_torn_stats_file_reads_as_not_yet_without_quarantine(tmp_path):
    """A stats snapshot torn at *any* byte length — including the
    0-byte file a crash right after ``open`` leaves — must read as
    "not yet" and must NOT be quarantined: the next periodic publish
    overwrites the same path, so renaming it aside would turn one torn
    write into a permanently missing channel."""
    mbox = WorkerMailbox(tmp_path / "w1")
    mbox.write_stats({"submitted": 7, "name": "w1"})
    raw = (mbox.root / "stats.npz").read_bytes()
    assert len(raw) > 8
    for cut in (0, 1, 8, len(raw) // 2, len(raw) - 1):
        (mbox.root / "stats.npz").write_bytes(raw[:cut])
        assert mbox.read_stats() is None, f"cut={cut}"
        assert (mbox.root / "stats.npz").exists(), \
            f"cut={cut}: torn snapshot was moved aside"
        assert not list(mbox.root.glob("*.corrupt")), \
            f"cut={cut}: snapshot channel was quarantined"
    # the writer's next publish repairs the channel in place
    mbox.write_stats({"submitted": 8, "name": "w1"})
    assert mbox.read_stats() == {"submitted": 8, "name": "w1"}


def test_torn_ready_marker_reads_as_not_yet(tmp_path):
    mbox = WorkerMailbox(tmp_path / "w1")
    mbox.write_ready({"pid": 123})
    raw = (mbox.root / "ready.npz").read_bytes()
    (mbox.root / "ready.npz").write_bytes(raw[: len(raw) // 3])
    assert mbox.read_ready() is None
    assert (mbox.root / "ready.npz").exists()
    mbox.write_ready({"pid": 123})
    assert mbox.read_ready() == {"pid": 123}


def test_queue_channel_still_quarantines_and_empty_file_does_not_raise(
        tmp_path):
    """Queue channels (requests/responses) keep the quarantine
    discipline — and the 0-byte case (np.load raises ``EOFError``, not
    ``ValueError``) must not escape `read_message`."""
    p = tmp_path / "r1.npz"
    p.write_bytes(b"")                                 # the EOFError shape
    assert read_message(p) is None
    assert not p.exists() and p.with_suffix(".npz.corrupt").exists()
    p2 = tmp_path / "r2.npz"
    p2.write_bytes(b"PK\x03\x04 torn")
    assert read_message(p2) is None
    assert p2.with_suffix(".npz.corrupt").exists()
    # read_snapshot on the same garbage: None, file left in place
    p3 = tmp_path / "r3.npz"
    p3.write_bytes(b"PK\x03\x04 torn")
    assert read_snapshot(p3) is None
    assert p3.exists()


# ---- histogram mergeability -------------------------------------------------

BOUNDS = (0.001, 0.01, 0.1, 1.0, 10.0)


def _merged_vs_union(values, n_shards):
    """Split ``values`` round-robin over ``n_shards`` worker histograms,
    merge their counts into a fleet histogram, and return it alongside
    the union-stream oracle."""
    shards = [Histogram(f"w{i}", BOUNDS) for i in range(n_shards)]
    union = Histogram("union", BOUNDS)
    for i, v in enumerate(values):
        shards[i % n_shards].observe(v)
        union.observe(v)
    fleet = Histogram("fleet", BOUNDS)
    for sh in shards:
        fleet.merge_counts(sh.counts(), count=sh.count, sum=sh.sum,
                           min=sh.min, max=sh.max)
    return fleet, union


def test_histogram_merge_equals_union_stream_seeded():
    rng = random.Random(1234)
    values = [rng.lognormvariate(-3, 2.5) for _ in range(500)]
    for k in (1, 2, 3, 7):
        fleet, union = _merged_vs_union(values, k)
        assert fleet.counts() == union.counts()
        assert fleet.count == union.count
        assert fleet.sum == pytest.approx(union.sum)
        assert fleet.min == union.min and fleet.max == union.max
        for q in (0.5, 0.9, 0.99):
            assert fleet.quantile(q) == pytest.approx(union.quantile(q))


@given(st.lists(st.floats(min_value=1e-6, max_value=100.0,
                          allow_nan=False, allow_infinity=False),
                min_size=1, max_size=200),
       st.integers(min_value=1, max_value=8))
@settings(max_examples=50, deadline=None)
def test_histogram_merge_property(values, n_shards):
    fleet, union = _merged_vs_union(values, n_shards)
    assert fleet.counts() == union.counts()
    assert fleet.count == union.count
    assert fleet.quantile(0.99) == pytest.approx(union.quantile(0.99))


def test_histogram_merge_rejects_mismatched_edges():
    a = Histogram("a", (0.1, 1.0))
    b = Histogram("b", (0.1, 1.0, 10.0))
    with pytest.raises(ValueError, match="merge shape mismatch"):
        a.merge_counts(b.counts())


# ---- Prometheus exporter golden ---------------------------------------------

def test_render_prometheus_golden(fresh_registry):
    reg = fresh_registry
    reg.counter("difet.router.admitted").inc(41)
    reg.gauge("difet.fleet.replicas_ready").set(2)
    h = reg.histogram("difet.kernel.step_s", (0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 2.0, 99.0):              # one per region
        h.observe(v)
    golden = "\n".join([
        "# TYPE difet_fleet_replicas_ready gauge",
        "difet_fleet_replicas_ready 2",
        "# TYPE difet_kernel_step_s histogram",
        'difet_kernel_step_s_bucket{le="0.1"} 1',
        'difet_kernel_step_s_bucket{le="1"} 3',
        'difet_kernel_step_s_bucket{le="10"} 4',
        'difet_kernel_step_s_bucket{le="+Inf"} 5',
        "difet_kernel_step_s_sum 102.05",
        "difet_kernel_step_s_count 5",
        "# TYPE difet_router_admitted counter",
        "difet_router_admitted 41",
    ]) + "\n"
    assert render_prometheus(reg) == golden


def test_render_prometheus_empty_registry():
    assert render_prometheus(MetricsRegistry()) == ""


# ---- span wire format -------------------------------------------------------

def test_span_wire_roundtrip_rebase_and_pid():
    s = Span(name="exec", layer="batch", trace_id="t1-abc",
             span_id="s1", parent_id="b0", t0=10.0, t1=10.5,
             thread="runner", attrs=(("bucket", 32), ("obj", object())),
             pid=111)
    wire = span_to_wire(s)
    json.dumps(wire)                      # must be JSON-able (npz meta)
    back = span_from_wire(wire, dt=2.0, pid=222)
    assert back.name == "exec" and back.trace_id == "t1-abc"
    assert back.t0 == pytest.approx(12.0)
    assert back.t1 == pytest.approx(12.5)
    assert back.pid == 222                # aggregator stamp wins
    assert dict(back.attrs)["bucket"] == 32
    assert isinstance(dict(back.attrs)["obj"], str)   # stringified


def test_fleet_metric_name_mapping():
    assert fleet_metric_name("difet.scheduler.queue_s") \
        == "difet.fleet.scheduler.queue_s"
    assert fleet_metric_name("difet.fleet.already") \
        == "difet.fleet.difet.fleet.already"
    assert fleet_metric_name("other.thing") == "difet.fleet.other.thing"


# ---- shipper -> aggregator roundtrip ----------------------------------------

def test_ship_and_aggregate_roundtrip(tmp_path, fresh_registry):
    """Two workers' delta shipments over a real mailbox merge into the
    parent registry: counter deltas accumulate, gauges sum per-worker
    last values, histogram totals equal the per-worker ledger, spans
    arrive pid-stamped, and a replayed payload is dropped by its
    sequence number (never double-counted)."""
    worker_reg = MetricsRegistry()
    (tmp_path / "d").mkdir(exist_ok=True)
    rec = FlightRecorder(capacity=64, dump_dir=str(tmp_path / "d"))
    mbox = WorkerMailbox(tmp_path / "w1")
    shipper = TelemetryShipper(mbox, "w1", registry=worker_reg,
                               recorder=rec, interval_s=0.0)

    worker_reg.counter("difet.cache.disk_hits").inc(3)
    worker_reg.gauge("difet.scheduler.queue_depth").set(5)
    h = worker_reg.histogram("difet.kernel.step_s", BOUNDS)
    h.observe(0.05)
    h.observe(0.5)
    prev = obs_trace.set_recorder(rec)
    try:
        obs_trace.emit_span("exec", "batch", 1.0, 1.5, trace_id="tA")
    finally:
        obs_trace.set_recorder(prev)
    assert shipper.ship() == 1
    worker_reg.counter("difet.cache.disk_hits").inc(2)   # second interval
    h.observe(7.0)
    assert shipper.ship() == 2
    assert shipper.ship() is None                        # nothing new

    payloads = mbox.collect_telemetry()
    assert [p["seq"] for p in payloads] == [1, 2]
    assert not list(mbox.tele.glob("*.npz"))             # queue drained

    parent_reg = MetricsRegistry()
    agg = TelemetryAggregator(parent_reg)
    assert agg.ingest(payloads) == 2
    assert parent_reg.counter("difet.fleet.cache.disk_hits").value == 5
    assert parent_reg.gauge(
        "difet.fleet.scheduler.queue_depth").value == 5
    fleet_h = parent_reg.histogram("difet.fleet.kernel.step_s", BOUNDS)
    assert fleet_h.count == 3 == agg.fleet_counts()["difet.kernel.step_s"]
    assert fleet_h.counts() == h.counts()
    assert fleet_h.min == h.min and fleet_h.max == h.max
    [span] = list(agg.spans)
    assert span.trace_id == "tA" and span.pid == os.getpid()
    # same process -> wall/mono anchors agree -> rebase is an identity
    assert span.t0 == pytest.approx(1.0, abs=0.05)

    # replay: a crash between collect and unlink re-delivers payloads —
    # sequence numbers make ingestion idempotent
    assert agg.ingest(payloads) == 0
    assert agg.dropped == 2
    assert parent_reg.counter("difet.fleet.cache.disk_hits").value == 5
    assert fleet_h.count == 3

    # a second worker's gauge sums with the first's
    agg.ingest([{"worker": "w2", "pid": 999, "seq": 1,
                 "wall_minus_mono": time.time() - time.monotonic(),
                 "gauges": {"difet.scheduler.queue_depth": 7.0},
                 "counters": {}, "hists": {}, "spans": [], "dumps": {}}])
    assert parent_reg.gauge(
        "difet.fleet.scheduler.queue_depth").value == 12


def test_final_flush_always_publishes_and_carries_dumps(tmp_path):
    reg = MetricsRegistry()
    (tmp_path / "d").mkdir(exist_ok=True)
    rec = FlightRecorder(capacity=16, dump_dir=str(tmp_path / "d"))
    rec.dump_on("shed-queue_full")
    mbox = WorkerMailbox(tmp_path / "w1")
    shipper = TelemetryShipper(mbox, "w1", registry=reg, recorder=rec)
    assert shipper.ship(final=True) == 1                 # empty but final
    [p] = mbox.collect_telemetry()
    assert p["final"] is True
    assert "shed-queue_full" in p["dumps"]
    agg = TelemetryAggregator(MetricsRegistry())
    agg.ingest([p])
    assert agg.worker_final["w1"] is True
    assert "shed-queue_full" in agg.worker_dumps["w1"]


# ---- SLO burn-rate monitor --------------------------------------------------

def test_burn_rate_monitor_alerts_once_and_dedupes_dump(tmp_path):
    clock = [0.0]
    hist = Histogram("lat", (0.01, 0.1, 1.0))
    policy = SloPolicy(latency_slo_s=0.1, objective=0.9,
                       fast_window_s=5.0, slow_window_s=60.0,
                       fast_burn=2.0, slow_burn=1.5)
    (tmp_path / "d").mkdir(exist_ok=True)
    rec = FlightRecorder(capacity=32, dump_dir=str(tmp_path / "d"))
    prev = obs_trace.set_recorder(rec)
    try:
        mon = BurnRateMonitor(hist, policy=policy,
                              clock=lambda: clock[0])
        for _ in range(50):                    # healthy: all within SLO
            hist.observe(0.005)
        clock[0] = 10.0
        r = mon.tick()
        assert not r["alerting"] and r["dump"] is None
        assert r["burn_fast"] == pytest.approx(0.0)
        assert r["p99_fast"] is not None and r["p99_fast"] <= 0.1

        for _ in range(50):                    # cliff: everything slow
            hist.observe(0.5)
        clock[0] = 20.0
        r1 = mon.tick()
        assert r1["alerting"] and r1["burn_fast"] > 2.0
        assert r1["dump"] and os.path.exists(r1["dump"])
        clock[0] = 21.0
        r2 = mon.tick()                        # still burning: no 2nd dump
        assert r2["alerting"] and r2["dump"] is None
        assert list(rec.dumps) == [BurnRateMonitor.DUMP_REASON]
        assert mon.alerts == 2
    finally:
        obs_trace.set_recorder(prev)


def test_burn_rate_counts_sheds_as_bad_events(tmp_path):
    """Sheds burn error budget even when every *served* request is
    fast — the SLO is over admission outcomes, not just latencies."""
    clock = [0.0]
    hist = Histogram("lat", (0.01, 0.1, 1.0))
    shed = obs_metrics.Counter("difet.router.shed.queue_full")
    policy = SloPolicy(latency_slo_s=0.1, objective=0.9,
                       fast_window_s=5.0, slow_window_s=60.0,
                       fast_burn=2.0, slow_burn=1.5)
    mon = BurnRateMonitor(hist, shed_counters=[shed], policy=policy,
                          clock=lambda: clock[0])
    for _ in range(10):
        hist.observe(0.005)
    shed.inc(90)                                       # 90% shed rate
    clock[0] = 10.0
    r = mon.tick()
    assert r["alerting"]
    assert r["burn_fast"] == pytest.approx((90 / 100) / 0.1)


# ---- cross-process trace stitch (proc fleet, telemetry on) ------------------

def test_proc_fleet_stitched_trace_two_worker_pids(tmp_path, flight,
                                                   fresh_registry):
    """End-to-end over real worker processes: two proc replicas with the
    telemetry plane on serve traced requests; the stitched Chrome trace
    must validate, contain spans from both worker pids (neither being
    the parent's), and >=1 admission-minted trace id must appear in both
    a parent admit span and a worker-side exec span."""
    from repro.data.landsat import synthetic_scene
    from repro.serve import Fleet, FleetConfig, ServeConfig
    from repro.configs.difet_paper import DifetConfig

    base = DifetConfig(tile=32, halo=8, max_keypoints_per_tile=16)
    cfg = FleetConfig(
        serve=ServeConfig(base=base, buckets=(32,), max_batch=4,
                          max_batch_delay_s=0.005, cache_entries=64),
        initial_replicas=2, min_replicas=1, max_replicas=2,
        warm_algorithm_sets=(("harris",),),
        cache_dir=str(tmp_path / "cache"),
        lease_dir=str(tmp_path / "leases"),
        transport_dir=str(tmp_path / "mbox"),
        proc=True, lease_ttl_s=5.0, heartbeat_interval_s=0.1,
        telemetry=True, telemetry_interval_s=0.05)
    fleet = Fleet(cfg)
    try:
        assert fleet.telemetry is not None
        tiles = [synthetic_scene(32, 32, 900 + i) for i in range(8)]
        handles = [fleet.submit(t, ("harris",), scene_key=f"sc-{i}")
                   for i, t in enumerate(tiles)]
        for h in handles:
            h.result(120)
    finally:
        fleet.close()          # drains -> final flush -> last poll

    agg = fleet.telemetry
    worker_pids = {s.pid for s in agg.spans} - {0, os.getpid()}
    assert len(worker_pids) == 2, f"worker pids seen: {worker_pids}"

    stitched = agg.stitched_spans(flight.spans())
    doc = spans_to_chrome(stitched)
    assert validate_chrome_trace(
        doc, required_layers=("router", "scheduler", "batch")) == []
    # one trace id joins the parent's admission to the worker's exec
    admit = {s.trace_id for s in flight.spans()
             if s.name == "admit" and s.trace_id}
    execs = {s.trace_id for s in agg.spans
             if s.name == "exec" and s.trace_id}
    assert admit & execs, (sorted(admit)[:4], sorted(execs)[:4])
    # exact merge: fleet totals == summed worker ledgers
    reg = obs_metrics.registry().metrics()
    ledger = agg.fleet_counts()
    assert ledger, "no worker histograms aggregated"
    for name, total in ledger.items():
        assert reg[fleet_metric_name(name)].count == total, name
