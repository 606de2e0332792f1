"""Cross-process replica fleet + SLO autoscaler + chaos harness.

Fault types exercised (via ``tests/chaos.py`` → `repro/serve/chaos.py`,
the same primitives ``launch/fleet.py --kill-after`` drives):

* real ``kill -9`` (parent-inflicted and worker self-inflicted)
* delayed/stalled heartbeats on a *live* process
* partitioned (unreachable) shared cache directory
* torn ``.npz`` writes (requests, cache entries, dead-writer tmps)
* withheld responses (work finished but not published across a kill)

Every recovery path must be *bit-identical*: re-admitted, re-executed,
or disk-served results all match the direct engine oracle.
"""
import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import numpy as np
import pytest

from chaos import (ChaosPlan, assert_results_equal, cache_partition,
                   clear_plan, direct_extract, read_plan, sigkill,
                   tear_file, wait_until, write_plan)
from repro.configs.difet_paper import DifetConfig
from repro.data.landsat import synthetic_scene
from repro.obs import metrics as obs_metrics
from repro.serve import (DiskCacheTier, Fleet, FleetConfig,
                         ProcReplicaClient, ServeConfig, WorkerMailbox)
from repro.serve.fleet import DEAD, READY, RETIRED
from repro.serve.proc import (serve_config_from_json, serve_config_to_json)
from repro.serve.scheduler import ReplicaDied
from repro.serve.transport import (encode_message, read_message,
                                   write_message)

BASE = DifetConfig(tile=32, halo=8, max_keypoints_per_tile=16)
SRC = Path(__file__).resolve().parents[1] / "src"


def serve_cfg(**kw) -> ServeConfig:
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_batch_delay_s", 0.005)
    kw.setdefault("cache_entries", 64)
    return ServeConfig(base=BASE, buckets=(32,), **kw)


def spawn_worker(tmp_path, name="w1", *, lease_ttl_s=5.0,
                 heartbeat_interval_s=0.1) -> ProcReplicaClient:
    client = ProcReplicaClient.spawn(
        name, tmp_path / "mbox" / name, serve_cfg(), tmp_path / "leases",
        lease_ttl_s=lease_ttl_s, heartbeat_interval_s=heartbeat_interval_s,
        warm_algorithm_sets=(("harris",),))
    client.wait_ready(180.0)
    return client


def proc_fleet_cfg(tmp_path, n, *, lease_ttl_s=0.6, **kw) -> FleetConfig:
    defaults = dict(
        serve=serve_cfg(), initial_replicas=n, min_replicas=1,
        max_replicas=max(n, 2), warm_algorithm_sets=(("harris",),),
        cache_dir=str(tmp_path / "cache"),
        lease_dir=str(tmp_path / "leases"),
        transport_dir=str(tmp_path / "mbox"),
        proc=True, lease_ttl_s=lease_ttl_s, heartbeat_interval_s=0.1)
    defaults.update(kw)
    return FleetConfig(**defaults)


def thread_fleet_cfg(**kw) -> FleetConfig:
    defaults = dict(
        serve=serve_cfg(cache_entries=0), initial_replicas=1,
        min_replicas=1, max_replicas=2,
        warm_algorithm_sets=(("harris",),),
        scale_up_queue_per_replica=1e9,     # isolate the SLO trigger
        scale_down_queue_per_replica=2.0, scale_down_grace_ticks=2)
    defaults.update(kw)
    return FleetConfig(**defaults)


# ---- transport: atomicity + crash discipline (no processes) ---------------

def test_message_roundtrip_bit_exact(tmp_path):
    meta = {"request_id": "r1", "algorithms": ["harris"], "trace_id": "t9"}
    arrays = {"image": np.arange(12, dtype=np.float32).reshape(3, 4),
              "count": np.array(7, np.int32),          # 0-d leaf
              "mask": np.array([True, False])}
    path = tmp_path / "m.npz"
    write_message(path, meta, arrays)
    assert not list(tmp_path.glob("*.tmp.*"))          # tmp committed away
    got_meta, got = read_message(path)
    assert got_meta == meta
    assert set(got) == set(arrays)
    for k in arrays:
        assert got[k].shape == np.asarray(arrays[k]).shape
        assert got[k].dtype == np.asarray(arrays[k]).dtype
        assert np.array_equal(got[k], arrays[k])
        assert not got[k].flags.writeable
    with pytest.raises(ValueError):                    # reserved slot
        encode_message({}, {"__meta__": np.zeros(1)})


def test_torn_request_is_quarantined_never_delivered(tmp_path):
    mbox = WorkerMailbox(tmp_path)
    mbox.send_request("r1", {"algorithms": ["harris"]},
                      {"image": np.zeros((32, 32), np.float32)})
    tear_file(mbox.req / "r1.npz", keep=40)            # torn-write fault
    assert mbox.claim_requests() == []                 # skipped, not served
    assert list(mbox.work.glob("*.corrupt"))           # quarantined
    assert mbox.pending_requests() == []               # never re-admitted
    mbox.send_request("r2", {"algorithms": ["harris"]},
                      {"image": np.zeros((32, 32), np.float32)})
    assert [rid for rid, _, _ in mbox.claim_requests()] == ["r2"]


def test_claimed_but_unanswered_is_enumerable_for_readmission(tmp_path):
    """A worker that dies after claiming leaves its claims visible to
    `pending_requests` — the router's re-admission inventory — while an
    answered claim is retired and its response persists."""
    mbox = WorkerMailbox(tmp_path)
    img = np.zeros((8, 8), np.float32)
    for rid in ("r1", "r2", "r3"):
        mbox.send_request(rid, {"algorithms": ["harris"]}, {"image": img})
    assert [r for r, _, _ in mbox.claim_requests()] == ["r1", "r2", "r3"]
    mbox.send_response("r2", {"status": "ok", "request_id": "r2"}, {})
    assert mbox.pending_requests() == ["r1", "r3"]
    assert mbox.has_response("r2")
    assert not (mbox.work / "r2.npz").exists()
    assert mbox.try_read_response("r2")[0]["status"] == "ok"


def test_serve_config_wire_roundtrip():
    cfg = serve_cfg(max_pending=99, use_pallas=False)
    wire = json.loads(json.dumps(serve_config_to_json(cfg)))
    assert serve_config_from_json(wire) == cfg


def test_spawn_leaves_the_platform_to_the_environment(tmp_path,
                                                     monkeypatch):
    """The worker inherits the parent's environment as it is: spawn no
    longer writes JAX_PLATFORMS (which sent every replica to the CPU)."""
    from repro.serve import proc
    seen = []

    class FakePopen:
        pid = 0

        def __init__(self, cmd, stdout=None, stderr=None, env=None):
            seen.append(env)

    monkeypatch.setattr(proc.subprocess, "Popen", FakePopen)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    ProcReplicaClient.spawn("w", tmp_path / "a", serve_cfg(), tmp_path / "l")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    ProcReplicaClient.spawn("w", tmp_path / "b", serve_cfg(), tmp_path / "l")
    assert "JAX_PLATFORMS" not in seen[0]
    assert seen[1]["JAX_PLATFORMS"] == "cpu"


def test_spawn_refuses_once_the_parent_holds_the_tpu(tmp_path, monkeypatch):
    """A chip serves one process: a parent with a TPU backend must not
    start a worker that would hang on the chip's lock."""
    from repro.serve import proc
    monkeypatch.setattr(proc, "_parent_backend", lambda: "tpu")
    monkeypatch.setattr(proc.subprocess, "Popen", lambda *a, **k: (
        pytest.fail("a worker was started")))
    with pytest.raises(RuntimeError, match="already holds the TPU"):
        ProcReplicaClient.spawn("w", tmp_path / "a", serve_cfg(),
                                tmp_path / "l")


def test_parent_backend_is_none_or_the_initialized_platform():
    from repro.serve import proc
    import jax
    assert proc._parent_backend() in (None, jax.default_backend())


def test_chaos_plan_file_lifecycle(tmp_path):
    assert read_plan(tmp_path) == ChaosPlan()          # absent: all off
    write_plan(tmp_path, ChaosPlan(heartbeat_stall_s=2.0,
                                   exit_after_requests=3))
    plan = read_plan(tmp_path)
    assert plan.heartbeat_stall_s == 2.0
    assert plan.exit_after_requests == 3
    assert plan.plan_time > 0                          # stamped from mtime
    assert plan.heartbeat_stalled(plan.plan_time + 1.0)
    assert not plan.heartbeat_stalled(plan.plan_time + 3.0)
    assert not plan.responses_held(plan.plan_time)     # fault not requested
    (tmp_path / "chaos.json").write_text("{not json")  # torn plan write
    assert read_plan(tmp_path) == ChaosPlan()          # never faults a worker
    clear_plan(tmp_path)
    assert read_plan(tmp_path) == ChaosPlan()


# ---- worker process: parity, drain, crash delivery ------------------------

def test_worker_parity_and_clean_drain(tmp_path):
    client = spawn_worker(tmp_path)
    try:
        tiles = [synthetic_scene(32, 32, 100 + i) for i in range(3)]
        client.register_scene("scene-a", tiles[0])     # parent-side registry
        handles = [client.submit("scene-a", ("harris",))]
        handles += [client.submit(t, ("harris",)) for t in tiles[1:]]
        for t, h in zip(tiles, handles):
            assert_results_equal(h.result(60).results, direct_extract(t))
        s = client.stats()
        assert s["alive"] and s["pid"] == client.pid
        assert s["queue_depth"] == 0
    finally:
        client.drain(60.0)
    assert client.proc.returncode == 0                 # clean exit


def test_drain_answers_every_accepted_request(tmp_path):
    client = spawn_worker(tmp_path)
    tiles = [synthetic_scene(32, 32, 200 + i) for i in range(6)]
    handles = [client.submit(t, ("harris",)) for t in tiles]
    client.drain(60.0)                                 # drain with work queued
    assert client.proc.returncode == 0
    for t, h in zip(tiles, handles):                   # zero dropped
        assert_results_equal(h.result(10).results, direct_extract(t))


def test_completed_before_crash_is_delivered_not_recomputed(tmp_path):
    """The response file is the commit point: work the worker finished
    before a ``kill -9`` is still delivered — a persisted response beats
    the dead flag."""
    client = spawn_worker(tmp_path)
    tile = synthetic_scene(32, 32, 300)
    h = client.submit(tile, ("harris",))
    wait_until(lambda: client.mailbox.has_response(h.request_id), 60,
               desc="response published")
    sigkill(client.pid)
    client.proc.wait(10)
    client.mark_dead()
    assert h.done() and not h.failed()                 # deliverable, not lost
    assert_results_equal(h.result(10).results, direct_extract(tile))


def test_exit_after_self_kill_leaves_pending_enumerable(tmp_path):
    """``exit_after_requests``: the worker ``os._exit(137)``s right after
    its N-th response — a deterministic self-``kill -9`` mid-stream.
    Published responses stay deliverable; the rest are enumerable for
    re-admission and their handles report ``failed()``."""
    client = spawn_worker(tmp_path)
    write_plan(client.root, ChaosPlan(exit_after_requests=2))
    tiles = [synthetic_scene(32, 32, 400 + i) for i in range(4)]
    handles = [client.submit(t, ("harris",)) for t in tiles]
    wait_until(lambda: client.proc.poll() is not None, 60,
               desc="worker self kill -9")
    assert client.proc.returncode == 137
    client.mark_dead()
    served = [(t, h) for t, h in zip(tiles, handles)
              if client.mailbox.has_response(h.request_id)]
    lost = [h for _, h in zip(tiles, handles)
            if not client.mailbox.has_response(h.request_id)]
    assert len(served) == 2 and len(lost) == 2
    for t, h in served:                                # still deliverable
        assert_results_equal(h.result(10).results, direct_extract(t))
    for h in lost:                                     # need re-admission
        assert h.failed()
        with pytest.raises(ReplicaDied):
            h.result(1.0)
    assert set(client.mailbox.pending_requests()) == \
        {h.request_id for h in lost}


# ---- fleet-level chaos: SIGKILL, stale leases, heartbeat stalls -----------

def test_proc_fleet_sigkill_stale_lease_readmits_bit_identical(tmp_path):
    """The tentpole chain: raw ``kill -9`` on a replica holding
    outstanding work → the parent learns of the death *only* through the
    stale lease → the victim's requests re-admit to the survivor and
    every accepted request completes bit-identically to the oracle."""
    m0 = obs_metrics.registry().snapshot()
    fleet = Fleet(proc_fleet_cfg(tmp_path, 2))
    try:
        for name in fleet.ready_replicas():            # keep work outstanding
            write_plan(fleet.transport_dir / name,
                       ChaosPlan(hold_responses_s=30.0))
        tiles = [synthetic_scene(32, 32, 500 + i) for i in range(8)]
        handles = [fleet.submit(t, ("harris",), scene_key=f"scene-{i}")
                   for i, t in enumerate(tiles)]
        victim = next(iter(fleet.router._outstanding.values())).replica
        fleet.sigkill_replica(victim)                  # no cooperative path
        for name in fleet.ready_replicas():
            clear_plan(fleet.transport_dir / name)

        def detected():
            fleet.maintenance_tick()
            return fleet.replicas[victim].state == DEAD
        wait_until(detected, 20, desc="stale-lease death detection")

        assert victim not in fleet.router.replica_names()
        results = [h.result(90) for h in handles]      # zero accepted lost
        assert len(results) == len(tiles)
        for t, r in zip(tiles, results):
            assert_results_equal(r.results, direct_extract(t))
        m1 = obs_metrics.registry().snapshot()
        assert (m1.get("difet.fleet.stale_lease_deaths", 0)
                - m0.get("difet.fleet.stale_lease_deaths", 0)) >= 1
        assert fleet.router.readmitted >= 1
    finally:
        fleet.close()


def test_heartbeat_stall_live_worker_declared_dead_and_reaped(tmp_path):
    """Delayed-heartbeat fault: the worker process is alive and well but
    stops refreshing its lease — indistinguishable from a hang to the
    control plane, so the fleet must declare it dead, reap the zombie,
    and keep serving from the survivor."""
    fleet = Fleet(proc_fleet_cfg(tmp_path, 2))
    try:
        victim = sorted(fleet.ready_replicas())[0]
        client = fleet.replicas[victim].service
        assert client.alive()
        write_plan(fleet.transport_dir / victim,
                   ChaosPlan(heartbeat_stall_s=60.0))

        def detected():
            fleet.maintenance_tick()
            return fleet.replicas[victim].state == DEAD
        wait_until(detected, 20, desc="stale lease on a live process")
        wait_until(lambda: not client.alive(), 10, desc="zombie reaped")
        assert victim not in fleet.router.replica_names()
        tile = synthetic_scene(32, 32, 601)            # survivor still serves
        assert_results_equal(
            fleet.extract(tile, ("harris",), timeout=60).results,
            direct_extract(tile))
    finally:
        fleet.close()


# ---- shared disk tier under faults (satellite: concurrent writers) --------

def test_cache_partition_degrades_to_compute(tmp_path):
    root = tmp_path / "tier"
    tier = DiskCacheTier(root)
    key = ("digest", "harris", "cfg")
    val = {"x": np.ones((3,), np.float32)}
    with cache_partition(root):
        tier.put(key, val)                             # absorbed, no raise
        assert tier.get(key) is None                   # miss, no raise
    assert tier.errors >= 1 and tier.stats()["errors"] >= 1
    tier.put(key, val)                                 # partition healed
    assert np.array_equal(tier.get(key)["x"], val["x"])


def test_concurrent_cross_process_put_same_key_one_wins(tmp_path):
    """Two OS processes hammer `DiskCacheTier.put` on the same content
    key with distinguishable values: the atomic-rename discipline means
    the surviving entry is always one writer's *complete* value, never
    an interleaving, and no tmp litter leaks."""
    script = textwrap.dedent("""
        import sys
        import numpy as np
        from repro.serve.cache import DiskCacheTier
        tier = DiskCacheTier(sys.argv[1])
        key = ("tile-digest", "harris", "cfg")
        val = {"x": np.full(256, float(sys.argv[2]), np.float32)}
        for _ in range(40):
            tier.put(key, val)
    """)
    env = dict(os.environ, PYTHONPATH=str(SRC))
    procs = [subprocess.Popen([sys.executable, "-c", script,
                               str(tmp_path), fill], env=env)
             for fill in ("1.0", "2.0")]
    for p in procs:
        assert p.wait(120) == 0
    tier = DiskCacheTier(tmp_path)
    got = tier.get(("tile-digest", "harris", "cfg"))["x"]
    assert got.shape == (256,) and got.dtype == np.float32
    assert np.all(got == got[0]) and got[0] in (1.0, 2.0)   # one writer won
    assert not list(Path(tmp_path).glob("*/*.tmp.*"))       # no torn tmps


def test_torn_cache_writes_read_as_miss(tmp_path):
    """A killed writer's leftover private tmp is never served, and a
    committed entry torn after the fact reads as a miss (and is
    dropped) — the tier always degrades to recompute."""
    tier = DiskCacheTier(tmp_path)
    key = ("digest-torn", "harris", "cfg")
    path = tier.path_for(key)
    path.parent.mkdir(parents=True, exist_ok=True)
    # fault 1: dead writer's tmp (SIGKILL mid-write, before the rename)
    (path.with_suffix(".tmp.99999.1")).write_bytes(b"partial dead write")
    assert tier.get(key) is None
    # fault 2: committed entry truncated in place
    tier.put(key, {"x": np.arange(64, dtype=np.float32)})
    tear_file(path, keep=48)
    assert tier.get(key) is None
    assert not path.exists()                           # torn entry dropped
    tier.put(key, {"x": np.arange(64, dtype=np.float32)})
    assert np.array_equal(tier.get(key)["x"],
                          np.arange(64, dtype=np.float32))


# ---- SLO autoscaler policy -------------------------------------------------

def test_slo_scale_up_on_p99_breach_records_decision():
    m0 = obs_metrics.registry().snapshot()
    fleet = Fleet(thread_fleet_cfg(slo_p99_s=1e-4))    # any latency breaches
    try:
        for i in range(4):
            fleet.extract(synthetic_scene(32, 32, 700 + i), ("harris",),
                          timeout=60)
        action = fleet.autoscale_tick()
        assert action.startswith("scale_up:")
        assert len(fleet.ready_replicas()) == 2
        ev = fleet.scale_events[-1]
        assert ev["action"] == "scale_up"
        assert ev["trigger"] == "p99_latency"          # not the queue path
        assert (ev["before"], ev["after"]) == (1, 2)
        assert ev["value"] > ev["slo_p99_s"] == fleet.cfg.slo_p99_s
        assert fleet.stats()["scale_events"][-1] == ev
        m1 = obs_metrics.registry().snapshot()
        assert (m1.get("difet.fleet.scale_up.p99_latency", 0)
                - m0.get("difet.fleet.scale_up.p99_latency", 0)) >= 1
    finally:
        fleet.close()


def test_slo_scale_down_drains_without_dropping():
    fleet = Fleet(thread_fleet_cfg(initial_replicas=2, slo_p99_s=1e9))
    try:
        tiles = [synthetic_scene(32, 32, 800 + i) for i in range(6)]
        handles = [fleet.submit(t, ("harris",), scene_key=f"s{i}")
                   for i, t in enumerate(tiles)]
        results = [h.result(60) for h in handles]
        assert fleet.autoscale_tick() == "hold"        # grace tick 1 of 2
        action = fleet.autoscale_tick()                # grace met → drain
        assert action.startswith("scale_down:")
        ev = fleet.scale_events[-1]
        assert ev["trigger"] == "slo_satisfied"
        assert (ev["before"], ev["after"]) == (2, 1)
        retired = action.split(":", 1)[1]
        assert fleet.replicas[retired].state == RETIRED
        for t, r in zip(tiles, results):               # nothing dropped
            assert_results_equal(r.results, direct_extract(t))
        assert fleet.autoscale_tick() == "hold"        # at min_replicas
        survivor = fleet.ready_replicas()
        assert len(survivor) == 1
        assert fleet.replicas[survivor[0]].state == READY
        fleet.extract(tiles[0], ("harris",), timeout=60)
    finally:
        fleet.close()
