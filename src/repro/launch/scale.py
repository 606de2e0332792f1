"""Horizontal-scaling study driver — the paper's experiment, end to end.

DIFET's Table 1 sweeps a fixed LandSat scene set over 1/2/4 Hadoop nodes
and reports wall-clock per algorithm.  This driver reproduces that shape
on the streaming ingest subsystem (docs/scaling.md): a fixed band-striped
scene set on disk, cut into fixed-shape tile batches by the streaming
pipeline (`data/pipeline.py`), with the worker axis swept 1→N.

Worker semantics: worker *i* of *W* owns the contiguous batch slice
``batch_slices(n_batches, W)[i]`` of the restart-deterministic manifest
order; it streams **only** its slice (scenes outside it are never read)
and extracts each batch with the same compiled program.  On a one-device
host the workers are *simulated* — each worker's slice is executed and
timed separately, and the reported t(W) is the slowest worker (the
straggler defines makespan, as in MapReduce).  On a multi-device host the
same batches are additionally sharded over the data mesh
(`DifetJob`-style ``batch_pspec`` inputs).

Every sweep verifies bit-parity: the per-batch results of every worker
count must equal the single-worker reference array-for-array — scaling is
a schedule change, never a numerics change.

    PYTHONPATH=src python -m repro.launch.scale --scenes 3 \
        --scene-size 512 --workers 1,2,4 --algorithms harris,sift
    PYTHONPATH=src python -m repro.launch.scale --smoke
"""
from __future__ import annotations

import argparse
import functools
import json
import time
from pathlib import Path
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from repro.configs.difet_paper import DifetConfig
from repro.core.engine import extract_features_multi, normalize_algorithms
from repro.data.landsat import BandSceneReader, write_synthetic_scene_set
from repro.data.pipeline import (Prefetcher, batch_slices, count_batches,
                                 iter_tile_batches)
from repro.launch.compile_cache import enable_compile_cache


def build_scene_set(root, n_scenes: int, scene_hw: Tuple[int, int]):
    """Materialize (or reopen) the fixed band-striped scene set and return
    its readers in deterministic name order — the order the manifest, and
    therefore every worker count, sees."""
    root = Path(root)
    dirs = sorted(d for d in root.glob("scene_*") if d.is_dir())
    if len(dirs) < n_scenes:
        write_synthetic_scene_set(root, n_scenes, *scene_hw)
        dirs = sorted(d for d in root.glob("scene_*") if d.is_dir())
    return [BandSceneReader(d) for d in dirs[:n_scenes]]


def make_batch_extractor(algorithms, cfg: DifetConfig, mesh=None,
                         use_pallas: bool = False):
    """jit-compiled fixed-shape batch extractor (the per-worker program).

    Returns ``fn(tiles, headers) -> {algorithm: result}``; with ``mesh``
    set the batch inputs carry explicit data-axis shardings, so on a
    multi-device host each worker's batches also split across devices.
    """
    import jax
    fn = functools.partial(extract_features_multi,
                           algorithms=tuple(algorithms), cfg=cfg,
                           use_pallas=use_pallas)
    if mesh is None:
        return jax.jit(fn)
    from jax.sharding import NamedSharding
    from repro.distributed.sharding import batch_pspec
    return jax.jit(fn, in_shardings=(
        NamedSharding(mesh, batch_pspec(mesh, 3)),
        NamedSharding(mesh, batch_pspec(mesh, 2))))


def run_worker(readers, cfg: DifetConfig, batch_tiles: int, fn,
               lo: int, hi: int, stripe_rows: Optional[int] = None,
               prefetch_depth: int = 2) -> Tuple[Dict[int, Dict], float]:
    """Execute one worker's contiguous batch slice ``[lo, hi)``.

    Streams the slice through the prefetch queue (tiling overlaps device
    compute), runs the compiled extractor per batch, and returns
    ``({batch_index: {algorithm: host result}}, wall_seconds)``.
    """
    import jax
    results: Dict[int, Dict] = {}
    t0 = time.perf_counter()
    with Prefetcher(iter_tile_batches(readers, cfg, batch_tiles,
                                      stripe_rows=stripe_rows,
                                      start=lo, stop=hi),
                    depth=prefetch_depth) as pf:
        for idx, bundle in pf:
            out = fn(bundle.tiles, bundle.headers)
            results[idx] = jax.device_get(out)
    return results, time.perf_counter() - t0


def _results_equal(a: Dict[int, Dict], b: Dict[int, Dict]) -> bool:
    """Bitwise comparison of two {batch: {alg: {key: array}}} result maps."""
    if a.keys() != b.keys():
        return False
    for idx in a:
        if a[idx].keys() != b[idx].keys():
            return False
        for alg in a[idx]:
            ra, rb = a[idx][alg], b[idx][alg]
            if ra.keys() != rb.keys():
                return False
            for k in ra:
                if not np.array_equal(np.asarray(ra[k]),
                                      np.asarray(rb[k])):
                    return False
    return True


def run_scaling(readers, cfg: DifetConfig, algorithms,
                workers: Sequence[int] = (1, 2, 4), batch_tiles: int = 8,
                mesh=None, use_pallas: bool = False,
                stripe_rows: Optional[int] = None, repeats: int = 1):
    """Sweep the worker count over a fixed scene set, one row per algorithm.

    For each algorithm: a single-worker reference pass establishes t(1)
    and the reference per-batch results; each worker count W partitions
    the batch manifest into W contiguous slices, executes and times every
    slice, and reports makespan t(W) = max over slices.  With
    ``repeats > 1`` every slice is executed that many times and its wall
    clock is the best of the repeats — the standard guard against
    one-off scheduler hiccups dominating short benchmark runs (parity is
    still checked on every repeat).  Returns a list of row dicts with
    ``t``/``speedup``/``efficiency`` per worker count, the grand total
    feature count, and ``parity`` (True iff every worker count's results
    were bit-identical to the reference).
    """
    algorithms = normalize_algorithms(algorithms)
    workers = tuple(workers)
    n_batches = count_batches([r.shape for r in readers], cfg, batch_tiles)
    if n_batches < max(workers):
        raise ValueError(
            f"{n_batches} batches cannot occupy {max(workers)} workers — "
            f"grow the scene set or shrink --batch-tiles")
    rows = []
    for alg in algorithms:
        fn = make_batch_extractor((alg,), cfg, mesh, use_pallas)
        # warm the one compiled program outside any timed region
        hw = cfg.tile + 2 * cfg.halo
        import jax
        jax.block_until_ready(fn(
            np.zeros((batch_tiles, hw, hw), np.float32),
            np.zeros((batch_tiles, 6), np.int32))[alg]["total_count"])
        times: Dict[int, float] = {}
        parity = True
        ref: Dict[int, Dict] = {}
        for w in workers:
            best_walls = None
            for _ in range(max(1, repeats)):
                worker_results: Dict[int, Dict] = {}
                walls = []
                for lo, hi in batch_slices(n_batches, w):
                    res, wall = run_worker(readers, cfg, batch_tiles, fn,
                                           lo, hi, stripe_rows)
                    worker_results.update(res)
                    walls.append(wall)
                best_walls = (walls if best_walls is None else
                              [min(a, b) for a, b in
                               zip(best_walls, walls)])
                if w == workers[0] and not ref:
                    ref = worker_results
                else:
                    parity = parity and _results_equal(ref, worker_results)
            times[w] = max(best_walls)     # straggler defines makespan
        t1 = times[workers[0]]
        total = int(np.sum([ref[i][alg]["total_count"]
                            for i in sorted(ref)]))
        rows.append({
            "algorithm": alg, "n_batches": n_batches,
            "t": times,
            "speedup": {w: t1 / times[w] for w in workers},
            "efficiency": {w: t1 / times[w] / w for w in workers},
            "total_count": total, "parity": parity,
        })
    return rows


def print_table(rows, workers) -> None:
    """Render the sweep as the paper's Table-1 shape (seconds + speedup)."""
    hdr = " ".join(f"t(w={w})" .rjust(9) for w in workers)
    spd = " ".join(f"s(w={w})".rjust(8) for w in workers)
    print(f"{'algorithm':12s} {hdr} {spd} {'count':>9s} parity")
    for r in rows:
        t = " ".join(f"{r['t'][w]:9.3f}" for w in workers)
        s = " ".join(f"{r['speedup'][w]:8.2f}" for w in workers)
        print(f"{r['algorithm']:12s} {t} {s} {r['total_count']:9d} "
              f"{r['parity']}")


def main(argv=None):
    """CLI entry point; ``--smoke`` is the CI gate (tiny set, parity must
    hold for every worker count)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenes", type=int, default=3)
    ap.add_argument("--scene-size", type=int, default=512)
    ap.add_argument("--tile", type=int, default=128)
    ap.add_argument("--halo", type=int, default=24)
    ap.add_argument("--batch-tiles", type=int, default=8)
    ap.add_argument("--workers", default="1,2,4")
    ap.add_argument("--algorithms", default="harris,fast,sift")
    ap.add_argument("--store", default="/tmp/difet_scale")
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--json", default=None,
                    help="also write the rows to this JSON path")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI mode: 2 scenes, workers 1,2; exits "
                         "non-zero unless every sweep is bit-exact")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.smoke:
        args.scenes, args.scene_size = 2, 160
        args.tile, args.halo, args.batch_tiles = 64, 16, 4
        args.workers, args.algorithms = "1,2", "harris,fast"
    workers = tuple(int(w) for w in args.workers.split(","))
    try:
        algorithms = normalize_algorithms(args.algorithms)
    except ValueError as e:
        ap.error(str(e))
    cfg = DifetConfig(tile=args.tile, halo=args.halo,
                      max_keypoints_per_tile=128)
    readers = build_scene_set(
        Path(args.store) / f"scenes_{args.scene_size}",
        args.scenes, (args.scene_size, args.scene_size))
    # on a multi-device host the batches additionally shard over a data
    # mesh; a single device compiles the same (unsharded) program
    import jax
    from repro.distributed.sharding import data_mesh
    mesh = data_mesh() if len(jax.devices()) > 1 else None
    print(f"[scale] {len(readers)} scenes of {args.scene_size}^2, "
          f"tile={args.tile}, batch={args.batch_tiles}, "
          f"workers={workers}, algorithms={','.join(algorithms)}, "
          f"devices={len(jax.devices())}")
    rows = run_scaling(readers, cfg, algorithms, workers,
                       batch_tiles=args.batch_tiles, mesh=mesh,
                       use_pallas=args.use_pallas)
    print_table(rows, workers)
    if args.json:
        Path(args.json).write_text(json.dumps(rows, indent=1, default=str))
        print(f"# wrote {args.json}")
    if not all(r["parity"] for r in rows):
        print("!! parity FAILED: some worker count changed results")
        raise SystemExit(1)
    if args.smoke:
        assert all(r["total_count"] > 0 for r in rows), \
            "smoke: no features extracted"
        print("[scale] smoke OK: bit-parity across worker counts, "
              f"{sum(r['total_count'] for r in rows)} features")
    return rows


if __name__ == "__main__":
    main()
