"""The reduction of a trace with the program's own spans and named scopes:
program spans label idle time before the benchmark's, scopes add up to
the busy time, a trace without them reduces as ``xtrace`` reduces it, and
the program's spans reach a ``jax.profiler`` trace on the CPU."""
import numpy as np
import pytest

from bench import program_trace as pt
from bench import xtrace
from bench.spec import Bench, ROOT

MS = 1e6          # nanoseconds


def _events():
    # window 0-100 ms.  Device 0 busy 10-40 and 60-70, device 1 busy 0-50.
    # The runner's program spans: idle 0-12, device_step 12-40,
    # deliver 40-55; the benchmark's store.get 45-58 (the generator's
    # bench.submit 80-90) overlaps them.
    return {
        "devices": {
            "/device:TPU:0": [(10 * MS, 30 * MS, "fusion.1"),
                              (20 * MS, 40 * MS, "fusion.2"),
                              (60 * MS, 70 * MS, "fusion.3")],
            "/device:TPU:1": [(0, 50 * MS, "fusion.1")],
        },
        "spans": [(0, 100 * MS, "bench.window"),
                  (45 * MS, 58 * MS, "bench.store.get"),
                  (80 * MS, 90 * MS, "bench.submit"),
                  (0, 12 * MS, "difet.scheduler.idle"),
                  (12 * MS, 40 * MS, "difet.kernel.device_step"),
                  (40 * MS, 55 * MS, "difet.batch.deliver")],
        "scopes": {"fusion.1": "difet.sift/describe",
                   "fusion.2": "difet.response.sift"},
    }


def test_program_spans_outrank_benchmark_spans_in_gap_labels():
    r = pt.reduce(_events())
    # device 1: 50-100 (mid 75: store.get is over, the window alone);
    # device 0: 70-100 (mid 85: bench.submit), 40-60 (mid 50: deliver,
    # though the shorter bench.store.get is open too), 0-10 (mid 5: idle)
    assert r["idle_gaps"][:4] == [
        ["bench.window", pytest.approx(0.050)],
        ["bench.submit", pytest.approx(0.030)],
        ["difet.batch.deliver", pytest.approx(0.020)],
        ["difet.scheduler.idle", pytest.approx(0.010)]]
    # the benchmark's own reduction labels the same gap with the shorter
    # benchmark span open at its middle
    assert xtrace.reduce(_events())["idle_gaps"][2][0] == "bench.store.get"


def test_idle_by_span_splits_every_idle_instant():
    r = pt.reduce(_events())
    # device 0 idle 0-10 (idle), 40-55 (deliver), 55-58 (store.get),
    # 58-60 / 70-80 / 90-100 (window), 80-90 (submit); device 1 idle
    # 50-55 (deliver), 55-58, 58-80 / 90-100, 80-90; mean over two
    want = {"difet.scheduler.idle": 0.010 / 2,
            "difet.batch.deliver": (0.015 + 0.005) / 2,
            "bench.store.get": (0.003 + 0.003) / 2,
            "bench.window": (0.022 + 0.032) / 2,
            "bench.submit": (0.010 + 0.010) / 2}
    assert r["idle_by_span"] == pytest.approx(want)
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_scopes_add_up_to_the_busy_time_of_every_device():
    r = pt.reduce(_events())
    # device 0: fusion.1 10-20, fusion.2 20-40, fusion.3 (unscoped)
    # 60-70; device 1: fusion.1 0-50; summed over the devices
    assert r["scopes"] == pytest.approx({"difet.sift/describe": 0.060,
                                         "difet.response.sift": 0.020,
                                         "other": 0.010})
    assert sum(r["scopes"].values()) == pytest.approx(
        sum(r["busy_by_device"].values()))
    assert r["op_scopes"]["fusion.3"] == "other"


@pytest.mark.parametrize("path,scope", [
    ("jit(f)/shard_map/vmap(difet.sift)/describe/jit(_take)/gather",
     "difet.sift/describe"),
    ("jit(step)/vmap(difet.response.fast)/jit(_fast)/add",
     "difet.response.fast"),
    ("jit(f)/difet.orb/reduce/top_k", "difet.orb/reduce"),
    ("jit(f)/vmap(difet.harris)", "difet.harris"),
    ("jit(f)/shard_map/copy", "other"),
    ("", "other"),
])
def test_scope_of_a_named_scope_path(path, scope):
    assert pt.scope_of(path) == scope


TRIMMED = ROOT / "bench/testdata/landsat8_x1_trimmed.xspace.txt"


def test_recorded_chip_trace_reduces_as_before_key_by_key():
    """A trace without program spans or scopes: every key of the
    benchmark's reduction reads the same, all busy time is ``other``."""
    from jax.profiler import ProfileData
    proto = ProfileData.from_text_proto(TRIMMED.read_text())
    before = xtrace.reduce(xtrace.events(proto))
    ev = pt.events(proto)
    assert ev["scopes"] == {}
    after = pt.reduce(ev)
    for key, value in before.items():
        assert after[key] == value, key
    assert after["scopes"] == {"other": pytest.approx(
        sum(before["busy_by_device"].values()))}
    # the bundle read holds most of the idle time, as its longest gap
    assert next(iter(after["idle_by_span"])) == "bench.store.get"
    assert sum(after["idle_by_span"].values()) == pytest.approx(
        before["window_s"] - before["busy_s"])


def test_numbers_are_none_without_their_input():
    from jax.profiler import ProfileData
    red = pt.reduce(pt.events(ProfileData.from_text_proto(
        TRIMMED.read_text())))
    assert all(v is None for k, v in pt.numbers({}, red).items()
               if k != "idle_under_program_share")
    assert pt.numbers({}, red)["idle_under_program_share"] == 0.0
    r = pt.reduce(_events())
    n = pt.numbers({"stats": {"batches": 4, "items": 8, "wait_s": 0.016},
                    "tiles": 2, "commits": 1}, r)
    assert n["queue_wait_ms.tile"] == pytest.approx(2.0)
    assert n["runner_host_ms_per_step.tile"] == pytest.approx(15.0 / 4)
    assert n["describe_ms_per_step.tile"] == pytest.approx(60.0 / 4)
    assert n["describe_ms_per_tile.batch"] == pytest.approx(30.0)
    assert n["job_host_ms_per_bundle.batch"] is None
    assert n["idle_under_program_share"] == pytest.approx(
        0.015 / (0.1 - 0.045))


def test_window_counters_carry_the_scheduler_wait(tmp_path):
    b = Bench()
    pt._carry_wait(b)
    delta = b.module("drivers", "service")._delta
    a = {"cache_hits": 0, "cache_misses": 0, "batches": 1, "submitted": 1,
         "shed": 0, "scheduler": {"items": 2, "wait_s": 0.5}}
    z = {**a, "batches": 3, "scheduler": {"items": 6, "wait_s": 0.75}}
    assert delta(a, z)["wait_s"] == pytest.approx(0.25)
    old = {k: v for k, v in a.items()} | {"scheduler": {"items": 2}}
    assert "wait_s" not in delta(old, old | {"scheduler": {"items": 3}})


def _profile(tmp_path, fn):
    from jax.profiler import ProfileData
    xtrace.start(tmp_path)
    try:
        fn()
    finally:
        path = xtrace.stop(tmp_path)
    return pt.events(ProfileData.from_file(str(path)))


def test_service_spans_reach_the_profiler(tmp_path):
    from repro.configs.difet_paper import DifetConfig
    from repro.data.landsat import synthetic_scene
    from repro.serve import FeatureService, ServeConfig
    svc = FeatureService(ServeConfig(
        base=DifetConfig(tile=32, halo=8, max_keypoints_per_tile=16),
        buckets=(32,), max_batch=4))
    try:
        svc.warmup([("harris",)])
        ev = _profile(tmp_path, lambda: [
            svc.extract(synthetic_scene(32, 32, i), ("harris",), timeout=60)
            for i in range(3)])
    finally:
        svc.close()
    names = {n for _, _, n in ev["spans"]}
    assert {"difet.scheduler.idle", "difet.scheduler.fill",
            "difet.batch.scatter", "difet.kernel.device_step",
            "difet.batch.deliver"} <= names, names
    steps = [(s, e) for s, e, n in ev["spans"]
             if n == "difet.kernel.device_step"]
    assert len(steps) == 3 and all(e > s for s, e in steps)


def test_job_spans_reach_the_profiler(tmp_path):
    from repro.configs.difet_paper import DifetConfig
    from repro.core.bundle import BundleStore, TileBundle
    from repro.core.job import DifetJob
    from repro.distributed.sharding import data_mesh
    cfg = DifetConfig(tile=32, halo=8, max_keypoints_per_tile=16)
    rng = np.random.RandomState(0)
    store = BundleStore(tmp_path / "store")
    headers = np.zeros((4, 6), np.int32)
    headers[:, 3:5] = 32
    for b in ("b0", "b1"):
        store.put(b, TileBundle(rng.rand(4, 48, 48).astype(np.float32),
                                headers, cfg))
    job = DifetJob(store, "harris,orb", mesh=data_mesh(1),
                   shards_per_bundle=2,
                   manifest_path=tmp_path / "job.manifest.json")
    ev = _profile(tmp_path / "trace", job.run)
    count = {}
    for _, _, n in ev["spans"]:
        count[n] = count.get(n, 0) + 1
    assert count == {"difet.job.bundle": 2, "difet.job.get": 2,
                     "difet.job.extract": 4, "difet.job.fetch": 4,
                     "difet.job.merge": 2, "difet.job.put": 2,
                     "difet.job.commit": 2}
    # each fetch lies inside an extract, each extract inside a bundle
    spans = sorted(ev["spans"])
    for child, parent in (("fetch", "extract"), ("extract", "bundle")):
        for s, e, n in spans:
            if n == f"difet.job.{child}":
                assert any(ps <= s and e <= pe for ps, pe, pn in spans
                           if pn == f"difet.job.{parent}")


@pytest.mark.parametrize("cell,over", [
    ("landsat8.all7.x1", {"config": {"tile": 32, "halo": 8,
                                     "bundle_tiles": 4,
                                     "max_keypoints_per_tile": 16},
                          "traffic": {"algorithms": ["harris", "orb"]}}),
    ("xyz256.steady", {"config": {"buckets": [32], "halo": 8,
                                  "max_keypoints_per_tile": 16}}),
])
def test_scopes_of_a_cells_programs_from_their_hlo(cell, over):
    """Each operation of a cell's compiled programs maps to the named
    scope its ``op_name`` metadata holds, under the name a trace gives
    the operation."""
    b = Bench()
    c = b.cell(cell)
    for part, keys in over.items():
        c[part] = {**c[part], **keys}
    (hlo,) = pt.cell_hlo(b, c)
    scopes = pt.op_scopes(hlo)
    algs = c["traffic"].get("algorithms") or c["traffic"]["algorithm_sets"][0]
    want = {f"difet.{a}/{s}" for a in algs for s in ("nms", "topk", "reduce")}
    want |= {f"difet.{a}/describe" for a in algs if a in ("orb", "sift")}
    assert want <= set(scopes.values()), set(scopes.values())
    for line in hlo.splitlines():
        if " = " in line and 'op_name="' in line and "difet." in line:
            op = xtrace.short(line.strip().removeprefix("ROOT "))
            assert op in scopes and " " in op
