"""DIFET bring-up smoke: the main path, end to end, on a TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the sharded job on four chips

One chip runs four phases in one process, through the entry points a user
calls:

* extract  — one synthetic Landsat-8 scene at the paper's size (7681 x
  7831, tile 512, halo 24: 256 tiles in one bundle of 4 shards) through
  ``launch/extract.py::build_store`` and ``core/job.py::DifetJob`` with all
  seven algorithms in one job; every algorithm must find features, and 8
  tiles are re-run on the host CPU as the reference.
* pallas   — the same 8 tiles with ``use_pallas=True`` against
  ``use_pallas=False``; every kernel the path takes must be a compiled
  Mosaic kernel, and each detector's path is printed.
* service  — an in-process ``serve/api.py::FeatureService`` (256 px
  web-map bucket, batch 8) answers 64 requests over mixed algorithm sets;
  every response must be bit-identical to a direct extraction.
* matcher  — ``kernels/ops.py::match_best2`` on all four paths (128-d L2,
  256-bit Hamming) and once through the default dispatch probe.

``--four-chips`` runs only the extract phase's scene through
``DifetJob(mesh=data_mesh(4))`` and ``DifetJob(mesh=data_mesh(1))`` and
compares the two bitwise.

Every phase runs even if an earlier one failed; any failure makes the
exit code non-zero and suppresses the last line.  The last line of stdout,
printed only when every phase passed, is the JSON device record.  The
seconds printed are smoke timings for bring-up, not benchmark metrics.
There is no CPU fallback: without a TPU the script exits non-zero.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import re
import sys
import tempfile
import time
import traceback
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs.difet_paper import PAPER_ALGORITHMS, PAPER_CONFIG  # noqa: E402
from repro.core import engine, nms  # noqa: E402
from repro.core.bundle import tile_scene  # noqa: E402
from repro.core.job import DifetJob  # noqa: E402
from repro.kernels import dispatch, ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.data.landsat import synthetic_scene  # noqa: E402
from repro.launch.extract import build_store  # noqa: E402

N_CHECK = 8                  # tiles re-run against a reference
# Two backends (XLA's CPU and TPU) or two paths (a Pallas kernel and the
# jnp ops) may round differently: FMA contraction, transcendentals and
# reduction order differ.  Response maps must agree to within fp32
# rounding, as |a - b| relative to the map's largest value; fp32 itself
# is that far from float64 on the CPU (2-3e-7 for Harris, Shi-Tomasi and
# FAST; 4.5e-3 for SURF, whose fp32 integral image sums 560^2 pixels).
RESPONSE_RTOL = {"surf": 1e-2}
RESPONSE_RTOL_DEFAULT = 1e-5
# Pixels beyond that band: a status that flips at a rounding-level tie
# (a SIFT extremum, a FAST arc) moves the response by a whole step; fp32
# vs float64 flips 2 of 1.25M SIFT pixels.  At most this share may.
DISAGREE_FRAC = 1e-5
# L2 matcher paths (the tolerance of tests/test_matcher.py)
L2_RTOL, L2_ATOL = 1e-5, 1e-4
# detector -> Pallas kernels its use_pallas=True path must take
DETECTOR_KERNELS = {
    "harris": {"harris"}, "shi_tomasi": {"shi_tomasi"},
    "fast": {"fast_score"}, "brief": {"fast_score"}, "orb": {"fast_score"},
    "sift": {"gaussian_blur"}, "surf": set(),
}


class PhaseFailed(AssertionError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise PhaseFailed(msg)


class CompileClock:
    """XLA compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def device_gate() -> dict:
    """The device record; exits non-zero when JAX finds no TPU."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU — JAX's default platform is "
                         f"{devs[0].platform!r}; this smoke runs only on "
                         f"the chip (no CPU fallback)")
    rec = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: platform={rec['platform']} kind={rec['kind']} "
          f"count={rec['count']}", flush=True)
    return rec


# ---- shared checks ----------------------------------------------------------

def response_maps(tiles, headers, cfg, algorithms, use_pallas=False):
    """Each distinct response map of ``algorithms``, with the algorithm's
    threshold and ownership mask: {alg: (resp, thr, mask)}."""
    out = {}
    for alg in algorithms:
        spec = engine.ALGORITHMS[alg]
        if any(engine.ALGORITHMS[a].response is spec.response for a in out):
            continue
        resp = jax.vmap(lambda t: spec.response(t, cfg, use_pallas))(tiles)
        mask = np.stack([np.asarray(nms.interior_mask(
            resp.shape[-2:], cfg.halo, h[3], h[4])) & (h[5] == 0)
            for h in np.asarray(headers)])
        out[alg] = (np.asarray(resp), spec.threshold(cfg), mask)
    return out


def compare_counts(label, got, want, maps_got, maps_want):
    """Per-algorithm count agreement.  A count is the number of interior
    pixels whose response exceeds the threshold, so two counts may differ
    only at pixels where the maps differ by rounding: pixels whose
    responses disagree beyond RESPONSE_RTOL (at most DISAGREE_FRAC of the
    interior), or pixels that differ at all within that band of the
    threshold.  The count gap must not exceed their number; where the
    maps are equal, it must be 0.  Every algorithm is printed before any
    failure is raised."""
    fails = []
    for alg in want:
        g, w = int(got[alg]), int(want[alg])
        src = next(a for a in maps_want
                   if engine.ALGORITHMS[a].response
                   is engine.ALGORITHMS[alg].response)
        ra, thr, mask = maps_want[src]
        rb = maps_got[src][0]
        rtol = RESPONSE_RTOL.get(src, RESPONSE_RTOL_DEFAULT)
        band = rtol * (float(np.abs(ra[mask]).max()) or 1.0)
        d = np.abs(ra - rb)[mask]
        disagree = int((d > band).sum())
        near = int(((np.abs(ra - thr) <= band) & (ra != rb) & mask).sum())
        flips = int((((ra > thr) != (rb > thr)) & mask).sum())
        print(f"  {label} {alg:10s} count {g} vs {w} (gap {abs(g - w)}); "
              f"response max|d| {d.max() / (band / rtol):.1e} of max|r|, "
              f"{disagree} px beyond {rtol:.0e}; threshold flips {flips}, "
              f"{near} differing px within the band of the threshold",
              flush=True)
        if w <= 0:
            fails.append(f"{alg}: reference found no features")
        if disagree > DISAGREE_FRAC * mask.sum():
            fails.append(f"{alg}: {disagree} px disagree beyond {rtol:.0e}")
        if abs(g - w) > near + disagree:
            fails.append(f"{alg}: count gap {abs(g - w)} > {near} + "
                         f"{disagree} pixels that rounding can flip")
    check(not fails, f"{label}: {'; '.join(fails)}")


def counts(res) -> dict:
    return {alg: int(np.asarray(r["total_count"])) for alg, r in res.items()}


# ---- phases -----------------------------------------------------------------

def check_tiles(cfg=PAPER_CONFIG, scene_hw=None, n_check=N_CHECK):
    """The first ``n_check`` tiles of the extract phase's scene (seed 0,
    as `build_store` makes it): the slice both reference checks re-run."""
    b = tile_scene(synthetic_scene(*(scene_hw or cfg.scene_hw), seed=0), cfg)
    return b.tiles[:n_check], b.headers[:n_check]


def phase_extract(workdir, tiles, headers, cfg=PAPER_CONFIG, scene_hw=None,
                  algorithms=PAPER_ALGORITHMS):
    """The batch job at published size, with ``tiles`` (its first tiles,
    `check_tiles`) re-run on the host CPU as the reference."""
    scene_hw = tuple(scene_hw or cfg.scene_hw)
    clock = CompileClock()
    t0 = time.perf_counter()
    store = build_store(Path(workdir) / "store", 1, scene_hw, cfg)
    (name,) = store.list()
    bundle = store.get(name)
    t_ingest = time.perf_counter() - t0
    print(f"  scene {scene_hw[0]}x{scene_hw[1]}: {len(bundle)} tiles of "
          f"{bundle.tiles.shape[1]}^2 px ({bundle.tiles.nbytes / 1e6:.0f} MB) "
          f"in bundle {name}, ingest {t_ingest:.1f} s", flush=True)
    job = DifetJob(store, ",".join(algorithms))
    t0 = time.perf_counter()
    summary = job.run()
    wall = time.perf_counter() - t0
    print(f"  job: {summary['bundles_done']}/{summary['bundles_total']} "
          f"bundles, {job.shards_per_bundle} shards, un-jitted engine path; "
          f"smoke timing: wall {wall:.1f} s, of which XLA compile "
          f"{clock.compile_s:.1f} s (set-up)", flush=True)
    for alg, s in summary["per_algorithm"].items():
        print(f"    {alg:10s} {s['grand_total']:>10d} features", flush=True)
        check(s["grand_total"] > 0, f"{alg}: no features over the scene")

    n_check = len(tiles)
    check(np.array_equal(bundle.tiles[:n_check], tiles)
          and np.array_equal(bundle.headers[:n_check], headers),
          "reference tiles are not the bundle's first tiles")
    chip = {alg: int(store.get_result(f"{name}.{alg}")["per_tile_count"]
                     [:n_check].sum()) for alg in algorithms}
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ref = counts(engine.extract_features_multi(tiles, headers,
                                                   algorithms, cfg))
        maps_ref = response_maps(tiles, headers, cfg, algorithms)
    maps_chip = response_maps(tiles, headers, cfg, algorithms)
    compare_counts("chip-vs-cpu", chip, ref, maps_chip, maps_ref)


def lowered_kernels(fn, *args) -> list:
    """Names of the Mosaic kernels in ``fn``'s lowered program."""
    text = jax.jit(fn).lower(*args).as_text()
    return re.findall(r'tpu_custom_call.*?kernel_name = "([^"]+)"', text)


def phase_pallas(tiles, headers, cfg=PAPER_CONFIG, compiled=True,
                 algorithms=PAPER_ALGORITHMS):
    """use_pallas=True vs False on the same tiles, with every kernel
    compiled (``compiled=False``: interpret mode, for hosts without a
    TPU)."""
    check(ops._interpret_default() is (not compiled),
          f"Pallas interpret mode resolved to {ops._interpret_default()}")
    with_k = engine.extract_features_multi(tiles, headers, algorithms, cfg,
                                           use_pallas=True)
    without = engine.extract_features_multi(tiles, headers, algorithms, cfg,
                                            use_pallas=False)
    compare_counts("pallas-vs-jnp", counts(with_k), counts(without),
                   response_maps(tiles, headers, cfg, algorithms, True),
                   response_maps(tiles, headers, cfg, algorithms))
    hw = tiles.shape[-1]
    for alg in algorithms:
        names = lowered_kernels(functools.partial(
            engine.extract_features_multi, algorithms=(alg,), cfg=cfg,
            use_pallas=True), tiles, headers)
        want = set(DETECTOR_KERNELS[alg])
        notes = []
        if alg == "sift":
            # keypoints come from octave 0 only (the engine takes [0]), so
            # jit drops octaves 1.. from the program: octave 0 decides
            if ops.scalespace_fits_vmem(hw, hw, cfg.scales_per_octave):
                want.add("scalespace_octave")
                notes.append(f"octave 0 ({hw} px): kernel")
            else:
                est = ops.scalespace_vmem_bytes(
                    hw, hw, cfg.scales_per_octave) / 2 ** 20
                notes.append(f"octave 0 ({hw} px): jnp, fused-octave "
                             f"estimate {est:.1f} MiB > "
                             f"{ops.VMEM_BUDGET_BYTES / 2 ** 20:.0f} MiB "
                             f"budget")
        if alg == "surf":
            notes.append("jnp (integral-image path is pallas-exempt)")
        path = ", ".join(f"{n} x{names.count(n)}" for n in sorted(set(names)))
        print(f"  {alg:10s} kernels: {path or 'none'}"
              + (f"; {'; '.join(notes)}" if notes else ""), flush=True)
        if compiled:
            check(set(names) == want, f"{alg}: kernels {sorted(set(names))}"
                                      f", expected {sorted(want)}")
        else:
            check(not names, f"{alg}: Mosaic kernels in interpret mode")


def phase_service(n_requests=64, bucket=256, batch=8, unique=32, seed=0):
    """The tile service: warm-up, mixed traffic, served-vs-direct parity."""
    from repro.serve import FeatureService, ServeConfig
    from repro.serve.trace import TraceConfig, tile_pool
    svc = FeatureService(ServeConfig(buckets=(bucket,), max_batch=batch))
    sets = [("sift",), ("orb",), ("harris", "orb", "shi_tomasi", "sift")]
    try:
        t0 = time.perf_counter()
        programs = svc.warmup(sets)
        warm = time.perf_counter() - t0
        clock = CompileClock()
        pool = tile_pool(TraceConfig(n_requests=1, seed=seed,
                                     tile_sizes=(bucket,),
                                     unique_scenes=unique))
        reqs = [((i * 7) % unique, sets[i % len(sets)])
                for i in range(n_requests)]
        handles = [svc.submit(pool[(s, bucket)], algs) for s, algs in reqs]
        resps = [h.result(300) for h in handles]
        stats = svc.stats()
        served_compile_s = clock.compile_s
    finally:
        svc.close()
    lat = np.asarray([r.timing["latency_s"] for r in resps])
    print(f"  bucket {bucket}, batch {batch}: {programs} programs, warm-up "
          f"{warm:.1f} s; {len(resps)} requests, {stats['batches']} device "
          f"batches, {stats['cache_hits']} cache hits; smoke timing: "
          f"latency p50 {np.percentile(lat, 50) * 1e3:.1f} ms, p99 "
          f"{np.percentile(lat, 99) * 1e3:.1f} ms, max "
          f"{lat.max() * 1e3:.1f} ms; XLA compile after warm-up "
          f"{served_compile_s:.1f} s", flush=True)
    cfg = svc.table.cfg_for(bucket)
    direct = {algs: jax.jit(functools.partial(
        engine.extract_features_multi, algorithms=algs, cfg=cfg))
        for algs in sets}
    mismatches = []
    for (s, algs), resp in zip(reqs, resps):
        tile, header = svc.table.pad_to_bucket(pool[(s, bucket)], bucket)
        want = jax.device_get(direct[algs](tile[None], header[None]))
        for alg in algs:
            for k, v in want[alg].items():
                got = resp.results[alg][k]
                if got.shape != v.shape or not np.array_equal(got, v):
                    bad = (got != v).reshape(got.shape[0], -1).any(-1) \
                        if got.shape == v.shape and got.ndim else [True]
                    slots = np.flatnonzero(bad)[:4].tolist()
                    mismatches.append(f"{resp.request_id} {alg}/{k} "
                                      f"slots {slots}")
    print(f"  served-vs-direct parity: {len(resps)} requests, "
          f"{len(mismatches)} mismatching arrays {mismatches[:8]}",
          flush=True)
    check(not mismatches, f"served != direct: {mismatches[:5]}")


def phase_matcher(workdir, nq=1024, nk=16384, seed=0):
    """Every matcher path on L2 128-d and Hamming 256-bit, then the
    default dispatch with its on-chip probe."""
    rng = np.random.RandomState(seed)
    valid = rng.rand(nk) < 0.9
    data = {
        "l2": (rng.randn(nq, 128).astype(np.float32),
               rng.randn(nk, 128).astype(np.float32)),
        "hamming": (rng.randint(0, 2 ** 32, (nq, 8), np.uint64)
                    .astype(np.uint32),
                    rng.randint(0, 2 ** 32, (nk, 8), np.uint64)
                    .astype(np.uint32)),
    }
    for metric, (q, db) in data.items():
        outs = {p: [np.asarray(a) for a in ops.match_best2(
            q, db, valid, metric=metric, path=p)] for p in ops.MATCH_PATHS}
        ref = outs["jnp_full"]
        for p, (b, s, i) in outs.items():
            rel = float(np.max(np.abs(b - ref[0]) / np.maximum(
                np.abs(ref[0]), 1e-30)))
            print(f"  {metric:7s} {p:16s} {nq} x {nk}: idx equal "
                  f"{np.array_equal(i, ref[2])}, best max rel diff "
                  f"{rel:.2e}", flush=True)
            check(np.array_equal(i, ref[2]), f"{metric} {p}: argbest differs")
            if metric == "hamming":
                check(np.array_equal(b, ref[0]) and np.array_equal(s, ref[1]),
                      f"hamming {p}: distances not bit-identical")
            else:
                check(np.allclose(b, ref[0], rtol=L2_RTOL, atol=L2_ATOL)
                      and np.allclose(s, ref[1], rtol=L2_RTOL, atol=L2_ATOL),
                      f"l2 {p}: distances beyond rtol {L2_RTOL}")
    # default dispatch: a fresh cache file, so the probe runs here
    os.environ[dispatch.CACHE_ENV] = str(Path(workdir) / "dispatch.json")
    dispatch.clear_memory_cache()
    for metric, (q, db) in data.items():
        before = dispatch.measure_count
        path = ops.match_path(nq, nk, q.shape[1], metric=metric)
        got = [np.asarray(a) for a in ops.match_best2(q, db, valid,
                                                      metric=metric)]
        ref = [np.asarray(a) for a in ops.match_best2(
            q, db, valid, metric=metric, path="jnp_full")]
        print(f"  {metric:7s} default dispatch -> {path} "
              f"({dispatch.measure_count - before} probe measurements)",
              flush=True)
        check(np.array_equal(got[2], ref[2]), f"{metric} dispatch: argbest")


def phase_four_chips(workdir, cfg=PAPER_CONFIG, scene_hw=None,
                     n_devices=4, algorithms=PAPER_ALGORITHMS):
    """The sharded job on ``n_devices`` against one device, bitwise."""
    from jax.sharding import NamedSharding
    from repro.distributed.sharding import batch_pspec, data_mesh
    scene_hw = tuple(scene_hw or cfg.scene_hw)
    store = build_store(Path(workdir) / "store", 1, scene_hw, cfg)
    (name,) = store.list()
    bundle = store.get(name)
    mesh = data_mesh(n_devices)
    x = jax.device_put(bundle.tiles[:n_devices],
                       NamedSharding(mesh, batch_pspec(mesh, 3)))
    devs = sorted({s.device.id for s in x.addressable_shards})
    print(f"  input shards on devices {devs}", flush=True)
    check(len(devs) == n_devices, f"shards on {devs}, want {n_devices} "
                                  f"distinct devices")
    results = {}
    for n in (n_devices, 1):
        clock = CompileClock()
        job = DifetJob(store, ",".join(algorithms), mesh=data_mesh(n),
                       manifest_path=Path(workdir) / f"mesh{n}.manifest")
        t0 = time.perf_counter()
        summary = job.run()
        print(f"  mesh of {n}: {summary['grand_total']} features; smoke "
              f"timing: wall {time.perf_counter() - t0:.1f} s, of which "
              f"XLA compile {clock.compile_s:.1f} s (set-up)", flush=True)
        results[n] = {alg: store.get_result(f"{name}.{alg}")
                      for alg in algorithms}
    diff = [f"{alg}/{k}" for alg, r in results[n_devices].items()
            for k, v in r.items() if not np.array_equal(v, results[1][alg][k])]
    print(f"  mesh {n_devices} vs mesh 1: {len(diff)} differing arrays "
          f"{diff[:5]}", flush=True)
    check(not diff, f"sharded job differs from one device: {diff[:5]}")


# ---- main -------------------------------------------------------------------

def run_phases(phases) -> list:
    """Run every phase; returns the names of those that failed."""
    failed = []
    for name, fn in phases:
        print(f"[{name}]", flush=True)
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:                      # reported, then exit 1
            traceback.print_exc()
            failed.append(name)
            print(f"[{name}] FAILED after {time.perf_counter() - t0:.1f} s",
                  flush=True)
        else:
            print(f"[{name}] ok ({time.perf_counter() - t0:.1f} s)",
                  flush=True)
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded job on four chips against "
                         "one chip")
    args = ap.parse_args(argv)
    rec = device_gate()
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    with tempfile.TemporaryDirectory(prefix="difet_smoke_") as work:
        if args.four_chips:
            phases = [("four-chips", lambda: phase_four_chips(
                Path(work) / "four"))]
        else:
            tiles, headers = check_tiles()
            phases = [
                ("extract", lambda: phase_extract(Path(work) / "extract",
                                                  tiles, headers)),
                ("pallas", lambda: phase_pallas(tiles, headers)),
                ("service", phase_service),
                ("matcher", lambda: phase_matcher(work)),
            ]
        failed = run_phases(phases)
    print(f"persistent compile cache hits: {clock.cache_hits}", flush=True)
    if failed:
        print(f"FAILED phases: {', '.join(failed)}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": rec}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
