"""Driver of the batch extraction job: ``core/job.py::DifetJob`` over a
``BundleStore`` of one seeded scene, on a mesh of the cell's chips.

Set-up makes the scene from the seed, cuts it into bundles of
``bundle_tiles`` tiles and writes them through the program's store.  A
store view lists the scene's bundles again and again under new item
names, so one ``DifetJob``, and so one compiled program, walks as many
bundles as the window can use.  The job then runs untimed items until
one runs without compiling or loading a program; the window runs the
same ``job.run()`` on, and closes at the first commit after ``--seconds``.
The check compares every window commit of a sample of the scene's
bundles, drawn from the seed, with the plain reference."""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import compare, scene
from bench.harness import log, memory_peak

DIFET_KEYS = ("scene_hw", "tile", "halo", "harris_k", "harris_threshold",
              "shi_tomasi_threshold", "fast_threshold", "fast_arc",
              "surf_hessian_threshold", "n_octaves", "scales_per_octave",
              "sift_contrast_threshold", "sift_edge_threshold",
              "brief_n_bits", "brief_patch", "orb_n_features",
              "max_keypoints_per_tile", "dtype")


class _Stop(Exception):
    pass


def _stop_after(n: int):
    seen = []

    def cb(name):
        seen.append(name)
        if len(seen) >= n:
            raise _Stop
    return cb


def make_store(root, base: list, n_items: int):
    """A ``BundleStore`` whose items ``itemNNNNN`` are the bundles
    ``base[i % len(base)]``: the program's store under it, each ``get``
    and ``put_result`` in a ``bench.store.*`` span and timed."""
    import jax
    from repro.core.bundle import BundleStore

    class ViewStore(BundleStore):
        def __init__(self):
            super().__init__(root)
            self.items = [f"item{i:05d}" for i in range(n_items)]
            self.store_s = []        # (call, seconds)

        def base_of(self, name):
            return base[int(name[4:]) % len(base)]

        def list(self):
            return list(self.items)

        def get(self, name):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.store.get"):
                b = super().get(self.base_of(name))
            self.store_s.append(("get", time.perf_counter() - t0))
            return b

        def put_result(self, name, result):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.store.put_result"):
                super().put_result(name, result)
            self.store_s.append(("put_result", time.perf_counter() - t0))

    return ViewStore()


def run(ctx) -> dict:
    import jax
    from repro.configs.difet_paper import DifetConfig
    from repro.core.bundle import TileBundle
    from repro.core.job import DifetJob
    from repro.distributed.sharding import data_mesh

    c, tr = ctx.config, ctx.traffic
    chips = ctx.workload["chips"]
    t = time.perf_counter()
    dcfg = DifetConfig(**{k: tuple(c[k]) if isinstance(c[k], list) else c[k]
                          for k in DIFET_KEYS})
    h, w = c["scene_hw"]
    img = scene.synthetic_scene(h, w, seed=scene.sub_seed(ctx.seed, 0))
    t = ctx.step("scene", t)
    tiles, headers = scene.tile_scene(img, c["tile"], c["halo"])
    del img
    parts = scene.bundles(tiles, headers, c["bundle_tiles"])
    names = [f"base{j}" for j in range(len(parts))]
    n_items = int(tr["items_per_second"] * ctx.seconds) + 16
    store = make_store(ctx.workdir / "store", names, n_items)
    for name, (bt, bh) in zip(names, parts):
        store.put(name, TileBundle(bt, bh, dcfg))
    t = ctx.step("store", t)
    mesh = data_mesh(chips)
    job = DifetJob(store, ",".join(tr["algorithms"]), mesh=mesh,
                   shards_per_bundle=c["shards_per_bundle"],
                   manifest_path=ctx.workdir / "job.manifest.json")
    for warm in range(1, 9):
        before = ctx.clock.programs
        with jax.profiler.TraceAnnotation("bench.warm"):
            try:
                job.run(progress=_stop_after(1))
            except _Stop:
                pass
        if ctx.clock.programs == before:
            break
    ctx.step(f"warm ({warm} items)", t)
    store.store_s.clear()

    commits = []

    def commit(name):
        commits.append((name, time.perf_counter()))
        if commits[-1][1] - ctx.t_window >= ctx.seconds:
            raise _Stop
    with ctx.window():
        with jax.profiler.TraceAnnotation("bench.job.run"):
            try:
                job.run(progress=commit)
            except _Stop:
                pass
    if len(commits) == 0 or commits[-1][1] - ctx.t_window < ctx.seconds:
        raise RuntimeError(f"the job ran out of items: {len(commits)} "
                           f"commits in the window")
    span = commits[-1][1] - ctx.t_window
    px = [scene.interior_px(parts[names.index(store.base_of(n))][1])
          for n, _ in commits]
    peak = memory_peak(mesh.devices.flat)
    log(f"window: {len(commits)} bundles committed in {span:.3f} s, "
        f"{sum(px) / 1e6:.2f} Mpx")
    del job
    gc.collect()

    # ---- check: every window commit of a sample of the scene's bundles
    rng = np.random.RandomState(scene.sub_seed(ctx.seed, 1))
    touched = sorted({store.base_of(n) for n, _ in commits})
    sample = sorted(str(b) for b in rng.choice(
        touched, min(tr["check_bundles"], len(touched)), replace=False))
    ref = ctx.reference()
    algs = tuple(tr["algorithms"])
    k = c["max_keypoints_per_tile"]
    t = time.perf_counter()
    pairs = []
    dev = jax.devices()[0]
    for b in sample:
        bt, bh = parts[names.index(b)]
        want = ref.features(bt, bh, c, algs, "float32", device=dev)
        want = {a: ref.job_result(want[a], k) for a in algs}
        if ctx.control:
            got = ref.features(bt, bh, c, algs, "bfloat16", device=dev)
            got = {a: ref.job_result(got[a], k) for a in algs}
        for n, _ in commits:
            if store.base_of(n) != b:
                continue
            if not ctx.control:
                got = {a: store.get_result(f"{n}.{a}") for a in algs}
            pairs += [(a, got[a], want[a]) for a in algs]
    tally = compare.tally(pairs)
    numbers = tally.numbers()
    ok, checks = compare.checks(numbers, c["limits"])
    log(f"check: {len(pairs) // len(algs)} commits of bundles {sample} "
        f"against the reference in {time.perf_counter() - t:.1f} s")
    return {"correct": ok and len(pairs) > 0, "checks": checks,
            "attempted": len(commits), "failed": 0,
            "memory_peak_bytes": peak,
            "commits": len(commits), "span_s": span,
            "scene_px": sum(px), "tiles": len(commits) * c["bundle_tiles"],
            "store_s": store.store_s, "numbers": numbers,
            "checked_commits": len(pairs) // len(algs),
            "keypoints": tally.keypoints()}
