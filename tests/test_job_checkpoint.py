"""Fault tolerance: job restart, manifest integrity, elastic rebalance."""
import json

import jax
import numpy as np
import pytest

from repro.configs.difet_paper import PAPER_ALGORITHMS, DifetConfig
from repro.core.bundle import BundleStore, bundle_scenes
from repro.core.job import DifetJob
from repro.data.landsat import synthetic_scene
from repro.obs import trace as obs_trace


def make_store(tmp_path, n_bundles=3):
    cfg = DifetConfig(tile=64, halo=16, max_keypoints_per_tile=32)
    store = BundleStore(tmp_path / "store")
    for i in range(n_bundles):
        store.put(f"b{i}", bundle_scenes(
            [synthetic_scene(100, 120, seed=i)], cfg))
    return store


def test_job_restart_after_failure_resumes_and_matches(tmp_path):
    store = make_store(tmp_path)
    # uninterrupted reference
    ref_store = make_store(tmp_path / "ref")
    ref = DifetJob(ref_store, "harris").run()

    job = DifetJob(store, "harris")
    with pytest.raises(RuntimeError, match="simulated worker failure"):
        job.run(simulate_failure_after=1)
    # manifest committed exactly one bundle
    m = json.loads(job.manifest_path.read_text())
    assert sum(m["done"].values()) == 1
    # restart (fresh object, as a new process would)
    job2 = DifetJob(store, "harris")
    summary = job2.run()
    assert summary["bundles_done"] == 3
    assert summary["grand_total"] == ref["grand_total"]
    assert summary["counts"] == {f"b{i}": ref["counts"][f"b{i}"]
                                 for i in range(3)}


def test_job_shard_merge_matches_unsharded(tmp_path):
    store = make_store(tmp_path, n_bundles=1)
    j1 = DifetJob(store, "fast", shards_per_bundle=1,
                  manifest_path=tmp_path / "m1.json")
    j4 = DifetJob(store, "fast", shards_per_bundle=4,
                  manifest_path=tmp_path / "m4.json")
    s1 = j1.run()
    # reset result by re-running with different manifest; results overwrite
    s4 = j4.run()
    assert s1["grand_total"] == s4["grand_total"]


def test_job_rebalance_partitions_everything(tmp_path):
    store = make_store(tmp_path, n_bundles=5)
    job = DifetJob(store, "harris")
    for n in (1, 2, 4):
        parts = job.rebalance(n)
        flat = sorted(b for p in parts for b in p)
        assert flat == sorted(job.manifest.remaining)


# ---- worker leases + elastic pools -----------------------------------------

def test_lease_board_acquire_refresh_steal(tmp_path):
    import time
    from repro.core.job import LeaseBoard
    lb = LeaseBoard(tmp_path / "leases", ttl_s=0.15)
    assert lb.acquire("item", "w0")
    assert not lb.acquire("item", "w1")      # live lease held elsewhere
    assert lb.acquire("item", "w0")          # own lease refreshes
    time.sleep(0.2)
    assert lb.acquire("item", "w1")          # stale lease stolen
    lb.release("item", "w0")                 # non-owner release: no-op
    assert not lb.acquire("item", "w2")
    lb.release("item", "w1")
    assert lb.acquire("item", "w2")


def test_elastic_worker_pool_resumes_after_crash(tmp_path):
    """A worker crash mid-pool + a dead worker's orphaned lease: restart
    with a *different* worker count drains everything, results identical
    to the uninterrupted single-worker job."""
    import time
    store = make_store(tmp_path, n_bundles=4)
    ref = DifetJob(make_store(tmp_path / "ref", n_bundles=4),
                   "harris").run()

    job = DifetJob(store, "harris", lease_ttl_s=0.1)
    with pytest.raises(RuntimeError, match="simulated worker failure"):
        job.run(worker_id="w0", simulate_failure_after=1)
    # a worker that claimed an item and died leaves an orphan lease
    remaining = job.manifest.remaining
    job.leases.acquire(remaining[0], "w_dead")
    time.sleep(0.15)
    # elastic restart: two fresh workers (new processes) share the pool
    s1 = DifetJob(store, "harris", lease_ttl_s=0.1).run(worker_id="w1")
    s2 = DifetJob(store, "harris", lease_ttl_s=0.1).run(worker_id="w2")
    assert s1["bundles_done"] == s2["bundles_done"] == 4
    assert s2["grand_total"] == ref["grand_total"]
    assert s2["counts"] == ref["counts"]


def test_concurrent_workers_partition_without_corruption(tmp_path):
    """Two threads running the same manifest concurrently: leases keep the
    work partitioned; every result lands; a final no-worker pass agrees
    with the uninterrupted reference bit-for-bit."""
    import threading
    store = make_store(tmp_path, n_bundles=6)
    ref = DifetJob(make_store(tmp_path / "ref", n_bundles=6),
                   "fast").run()

    def worker(wid):
        DifetJob(store, "fast").run(worker_id=wid)

    threads = [threading.Thread(target=worker, args=(f"w{i}",))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # every bundle has a committed result regardless of lease races
    assert all(store.has_result(f"b{i}.fast") for i in range(6))
    # the self-healing pass (re-marks any lost done-flags; no-op compute
    # at worst re-runs a deterministic item) matches the reference
    final = DifetJob(store, "fast").run()
    assert final["grand_total"] == ref["grand_total"]
    assert final["counts"] == ref["counts"]


def test_scaling_sweep_bit_identical_at_every_worker_count(tmp_path):
    """The paper's Table-1 sweep (`launch/scale.py`) over 1, 2 and 4
    workers: each worker count's per-batch results equal the one-worker
    run's, array for array — scaling is a schedule change, never a
    numerics change."""
    from repro.launch.scale import build_scene_set, run_scaling
    cfg = DifetConfig(tile=64, halo=16, max_keypoints_per_tile=32)
    readers = build_scene_set(tmp_path / "scenes", 2, (160, 160))
    rows = run_scaling(readers, cfg, "harris,brief", workers=(1, 2, 4),
                       batch_tiles=4)
    assert [r["algorithm"] for r in rows] == ["harris", "brief"]
    for r in rows:
        assert r["n_batches"] >= 4
        assert r["total_count"] > 0
        assert r["parity"], r["algorithm"]


def test_manifest_order_is_restart_deterministic(tmp_path):
    store = make_store(tmp_path, n_bundles=5)
    j1 = DifetJob(store, "harris")
    order1 = list(j1.manifest.bundle_names)
    j2 = DifetJob(store, "harris")       # fresh load from disk
    assert list(j2.manifest.bundle_names) == order1 == sorted(order1)


@pytest.mark.parametrize("alg", PAPER_ALGORITHMS)
def test_mesh_sharded_job_bit_identical(tmp_path, alg):
    """DifetJob with a (size-1 CPU) data mesh runs the jitted
    batch-sharded path; results must be bit-identical to the same jitted
    program without input shardings (sharding is a layout change, never a
    numerics change)."""
    import functools
    from repro.core.engine import extract_features_multi
    from repro.distributed.sharding import data_mesh
    store = make_store(tmp_path, n_bundles=2)
    meshed = DifetJob(store, alg, manifest_path=tmp_path / "mesh.json",
                      shards_per_bundle=1, mesh=data_mesh(1))
    meshed.run()
    for n in ("b0", "b1"):
        b = store.get(n)
        ref = jax.jit(functools.partial(
            extract_features_multi, algorithms=(alg,),
            cfg=b.cfg))(b.tiles, b.headers)
        got = store.get_result(f"{n}.{alg}")
        assert got.keys() == ref[alg].keys()
        for k in got:
            np.testing.assert_array_equal(
                got[k], np.asarray(ref[alg][k]), err_msg=f"{n}.{alg}.{k}")


def test_mesh_padding_slice_matches_unpadded(tmp_path):
    """Force the pad path: a fake 3-wide data axis on a 7-tile shard must
    slice back to exactly the unpadded result."""
    from repro.distributed.sharding import data_mesh
    store = make_store(tmp_path, n_bundles=1)
    job = DifetJob(store, "harris", manifest_path=tmp_path / "m.json",
                   shards_per_bundle=1, mesh=data_mesh(1))
    bundle = store.get("b0")
    n = len(bundle)
    ref = job._extract(bundle.tiles, bundle.headers, bundle.cfg)["harris"]
    # pretend the data axis is 3 wide: pad to the next multiple of 3
    job._data_size = lambda: 3
    job._sharded_fns.clear()
    padded = job._extract(bundle.tiles, bundle.headers,
                          bundle.cfg)["harris"]
    assert padded["per_tile_count"].shape[0] == n
    for k in ref:
        np.testing.assert_array_equal(np.asarray(padded[k]),
                                      np.asarray(ref[k]), err_msg=k)


# ---- read-ahead -------------------------------------------------------------

def _counter(name):
    from repro.obs import metrics as obs_metrics
    return obs_metrics.registry().counter(f"difet.job.{name}").value


def _readers():
    import threading
    return [t for t in threading.enumerate()
            if t.name.startswith("difet-job-reader")]


class _SequentialJob(DifetJob):
    """The same job without a load stage: ``run`` reads each bundle in
    ``process``, one item after another (the reference loop)."""
    load = None

    def process(self, name):
        super().process(name, DifetJob.load(self, name))


def _results(store, names, algs):
    return {(n, a, k): v for n in names for a in algs
            for k, v in store.get_result(f"{n}.{a}").items()}


def _assert_same_results(got, want):
    assert got.keys() == want.keys()
    for key in want:
        np.testing.assert_array_equal(got[key], want[key], err_msg=str(key))


@pytest.mark.parametrize("meshed", [False, True])
@pytest.mark.parametrize("shards", [1, 4])
def test_readahead_matches_sequential(tmp_path, meshed, shards):
    """The read-ahead loop commits the same results, bit for bit, and the
    same manifest as the loop that reads each bundle in turn; every item
    after the first is handed over from the read-ahead."""
    from repro.distributed.sharding import data_mesh
    algs = ("harris", "fast")
    kw = dict(shards_per_bundle=shards,
              mesh=data_mesh(1) if meshed else None)
    seq_store = make_store(tmp_path / "seq")
    _SequentialJob(seq_store, ",".join(algs), **kw).run()
    store = make_store(tmp_path / "ahead")
    hits, dropped = _counter("readahead_hits"), _counter("readahead_dropped")
    job = DifetJob(store, ",".join(algs), **kw)
    prev = obs_trace.set_recorder(obs_trace.FlightRecorder())
    try:
        summary = job.run()
        spans = obs_trace.get_recorder().spans()
    finally:
        obs_trace.set_recorder(prev)
    assert summary["bundles_done"] == 3
    # one wait per bundle, and the last for the end of the items
    assert sum(sp.name == "wait_load" for sp in spans) == 3 + 1
    assert _counter("readahead_hits") - hits == 3 - 1
    assert _counter("readahead_dropped") == dropped
    assert not _readers()
    names = [f"b{i}" for i in range(3)]
    _assert_same_results(_results(store, names, algs),
                         _results(seq_store, names, algs))
    got = json.loads(job.manifest_path.read_text())
    want = json.loads((seq_store.root / "harris,fast.manifest.json")
                      .read_text())
    for m in (got, want):
        m.pop("started_at")
    assert got == want


class _Stop(Exception):
    pass


class _FailingStore(BundleStore):
    """A store whose ``get`` of one bundle raises, once."""

    def __init__(self, root, fail_on):
        super().__init__(root)
        self.fail_on = fail_on

    def get(self, name):
        if name == self.fail_on:
            self.fail_on = None
            raise OSError(f"unreadable bundle {name}")
        return super().get(name)


@pytest.mark.parametrize("exit_by,done,n_dropped", [
    ("progress", ["b0"], 1),
    ("simulated_failure", ["b0"], 1),
    ("load_error", ["b0", "b1"], 0),
])
def test_readahead_exit_joins_reader_and_resumes(tmp_path, exit_by, done,
                                                 n_dropped):
    """However ``run`` leaves, the items before the exit are committed,
    the reader thread is joined, a bundle it read ahead is dropped, and a
    restart completes the job bit-identically to an uninterrupted one."""
    names = [f"b{i}" for i in range(4)]
    ref_store = make_store(tmp_path / "ref", n_bundles=4)
    _SequentialJob(ref_store, "harris").run()
    make_store(tmp_path, n_bundles=4)
    store = _FailingStore(tmp_path / "store",
                          "b2" if exit_by == "load_error" else None)

    def stop(name):
        raise _Stop(name)

    dropped = _counter("readahead_dropped")
    job = DifetJob(store, "harris")
    if exit_by == "progress":
        with pytest.raises(_Stop):
            job.run(progress=stop)
    elif exit_by == "simulated_failure":
        with pytest.raises(RuntimeError, match="simulated worker failure"):
            job.run(simulate_failure_after=1)
    else:
        with pytest.raises(OSError, match="unreadable bundle b2"):
            job.run()
    m = json.loads(job.manifest_path.read_text())
    assert [n for n in names if m["done"][n]] == done
    assert all(store.has_result(f"{n}.harris") == (n in done)
               for n in names)
    assert not _readers()
    assert _counter("readahead_dropped") - dropped == n_dropped
    assert DifetJob(store, "harris").run()["bundles_done"] == 4
    assert not _readers()
    _assert_same_results(_results(store, names, ("harris",)),
                         _results(ref_store, names, ("harris",)))


def test_readahead_reads_only_leased_items(tmp_path):
    """Two concurrent pool workers with the read-ahead: each bundle is read
    only by a worker that holds its lease at that moment, and the merged
    results match the uninterrupted reference."""
    import threading
    from repro.core.job import LeaseBoard
    names = [f"b{i}" for i in range(6)]
    ref_store = make_store(tmp_path / "ref", n_bundles=6)
    _SequentialJob(ref_store, "fast").run()
    make_store(tmp_path, n_bundles=6)
    reads = []

    class WorkerStore(BundleStore):
        def __init__(self, root, worker):
            super().__init__(root)
            self.worker = worker

        def get(self, name):
            board = LeaseBoard(self.root / "fast.manifest.leases")
            reads.append((name, self.worker, board.holder(name)))
            return super().get(name)

    def worker(wid):
        DifetJob(WorkerStore(tmp_path / "store", wid), "fast").run(
            worker_id=wid)

    threads = [threading.Thread(target=worker, args=(f"w{i}",))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive()
    assert not _readers()
    assert {n for n, _, _ in reads} == set(names)
    for name, wid, holder in reads:
        assert holder is not None and holder[0] == wid, (name, wid, holder)
    store = BundleStore(tmp_path / "store")
    assert all(store.has_result(f"{n}.fast") for n in names)
    _assert_same_results(_results(store, names, ("fast",)),
                         _results(ref_store, names, ("fast",)))
