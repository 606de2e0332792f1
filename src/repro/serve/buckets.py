"""Shape buckets + the per-(bucket, algorithm-set) compile cache.

jit recompiles per input shape, and a public tile service sees arbitrary
tile sizes — unbounded shapes would mean unbounded compiles.  Incoming
tiles are therefore padded into a small static table of interior sizes
(the *buckets*); batches are always padded to the scheduler's fixed
``max_batch``; and the algorithm set is canonicalized — so the number of
compiled programs is exactly ``len(buckets) × len(distinct algorithm
sets)``, each compiled once (``CompileCache``), and ``warmup`` pre-pays
all of them before traffic arrives.

Padding reuses the engine's own convention: a request tile is treated as
a one-tile scene (`core/bundle.py::tile_scene`), giving a reflect-padded
halo ring and a header whose ``valid_h/valid_w`` confine detection to the
request's real pixels — bucket padding can never emit keypoints
(`nms.interior_mask`), so results are independent of which bucket a tile
landed in beyond the documented tile-size semantics.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.configs.difet_paper import DifetConfig
from repro.core.bundle import tile_scene
from repro.core.engine import make_serve_step
from repro.obs import metrics as obs_metrics
from repro.obs import profile as obs_profile
from repro.obs import trace as obs_trace


class BucketTable:
    """Static table of interior sizes; ``bucket_for`` picks the smallest
    bucket that holds a tile (None = bigger than every bucket, the caller
    splits it into a multi-tile scene request)."""

    def __init__(self, interiors: Sequence[int], base: DifetConfig):
        self.interiors: Tuple[int, ...] = tuple(sorted(set(int(i)
                                                           for i in interiors)))
        if not self.interiors:
            raise ValueError("bucket table needs at least one interior size")
        self.base = base
        self._cfgs: Dict[int, DifetConfig] = {}

    @property
    def halo(self) -> int:
        return self.base.halo

    def bucket_for(self, h: int, w: int) -> Optional[int]:
        side = max(int(h), int(w))
        for interior in self.interiors:
            if side <= interior:
                return interior
        return None

    def cfg_for(self, bucket: int) -> DifetConfig:
        if bucket not in self._cfgs:
            if bucket not in self.interiors:
                raise KeyError(f"{bucket} is not a bucket "
                               f"(table: {self.interiors})")
            self._cfgs[bucket] = dataclasses.replace(self.base, tile=bucket)
        return self._cfgs[bucket]

    def pad_to_bucket(self, gray: np.ndarray, bucket: int):
        """Pad one grayscale tile into its bucket canvas.  Returns
        ``(tile [hw, hw] float32, header [6] int32)`` with hw =
        bucket + 2*halo; the header's valid extent is the tile's own
        shape, so detection ignores the padding.  Output is bit-identical
        to ``tile_scene`` on the same tile (tested) — the fast path just
        skips ``np.pad``'s generic machinery, which dominated the
        per-request submit cost."""
        gray = np.asarray(gray, np.float32)
        h, w = gray.shape
        if min(h, w) < 2:
            raise ValueError(f"tile {h}x{w} too small: reflect padding "
                             f"needs at least 2 pixels per side")
        tile = _reflect_pad_fast(gray, bucket, self.halo)
        if tile is None:    # pad needs numpy's multi-bounce reflection
            b = tile_scene(gray, self.cfg_for(bucket))
            assert len(b) == 1, "tile exceeded its bucket"
            return b.tiles[0], b.headers[0]
        header = np.array([0, 0, 0, h, w, 0], np.int32)
        return tile, header


def _reflect_pad_fast(gray: np.ndarray, t: int, halo: int):
    """Single-bounce reflect pad of one tile to ``(t+2h) x (t+2h)`` —
    exactly ``np.pad(gray, ((h, h+t-H), (h, h+t-W)), 'reflect')`` (the
    ``tile_scene`` convention: axis 0 first, then axis 1 over the padded
    rows), hand-rolled as six slice copies.  Returns None when any pad
    width needs numpy's multi-bounce reflection (tiny tiles in big
    buckets) and the caller falls back to ``tile_scene``."""
    h, w = gray.shape
    pb, pr = halo + t - h, halo + t - w          # bottom / right pad widths
    if max(halo, pb) > h - 1 or max(halo, pr) > w - 1:
        return None
    hw = t + 2 * halo
    rows = np.empty((hw, w), np.float32)
    rows[halo:halo + h] = gray
    rows[:halo] = gray[halo:0:-1]
    rows[halo + h:] = gray[h - 2::-1][:pb]
    out = np.empty((hw, hw), np.float32)
    out[:, halo:halo + w] = rows
    out[:, :halo] = rows[:, halo:0:-1]
    out[:, halo + w:] = rows[:, w - 2::-1][:, :pr]
    return out


class CompileCache:
    """(bucket, algorithm-set) → jitted serving step; one program each.

    The scheduler pads every batch to ``max_batch`` rows, so each program
    sees exactly one input shape and jit-compiles exactly once.
    ``programs`` counts distinct programs built — the serving metric the
    benchmark reports as compile-cache size.

    ``device`` pins the programs to one device: `place` commits each
    batch to it before the call, so jit compiles for and runs on that
    device.  None leaves batches as host arrays, which jit puts on the
    default device."""

    def __init__(self, table: BucketTable, max_batch: int,
                 use_pallas: bool = False, device=None):
        self.table = table
        self.max_batch = int(max_batch)
        self.use_pallas = use_pallas
        self.device = device
        self._fns: Dict[tuple, object] = {}

    @property
    def programs(self) -> int:
        return len(self._fns)

    def keys(self):
        return sorted(self._fns)

    def get(self, bucket: int, algorithms: Tuple[str, ...]):
        key = (int(bucket), tuple(algorithms))
        fn = self._fns.get(key)
        if fn is None:
            fn = make_serve_step(key[1], self.table.cfg_for(key[0]),
                                 use_pallas=self.use_pallas)
            self._fns[key] = fn
        return fn

    def place(self, tiles, headers):
        """``(tiles, headers)`` as a program's arguments: committed to
        ``device`` when one is set, else unchanged."""
        if self.device is None:
            return tiles, headers
        return jax.device_put((tiles, headers), self.device)

    def empty_batch(self, bucket: int):
        """An all-padding batch at this bucket's device shape (header pad
        flag set, so nothing detects) — the warm-up input, also used by the
        scheduler runner as the canvas real tiles are scattered into."""
        hw = bucket + 2 * self.table.halo
        tiles = np.zeros((self.max_batch, hw, hw), np.float32)
        headers = np.zeros((self.max_batch, 6), np.int32)
        headers[:, 5] = 1
        return tiles, headers


def warmup(compile_cache: CompileCache,
           algorithm_sets: Sequence[Tuple[str, ...]],
           buckets: Optional[Sequence[int]] = None) -> int:
    """Warm-up driver: compile every (bucket, algorithm-set) pair by
    pushing one all-padding batch through each program, so no live request
    ever pays a compile.  Returns the number of compiled programs."""
    hist = obs_metrics.registry().histogram("difet.compile.program_s")
    for bucket in (buckets if buckets is not None
                   else compile_cache.table.interiors):
        tiles, headers = compile_cache.empty_batch(bucket)
        for algs in algorithm_sets:
            key = (int(bucket), tuple(algs))
            fresh = key not in compile_cache._fns
            fn = compile_cache.get(bucket, tuple(algs))
            t0 = time.monotonic()
            jax.block_until_ready(fn(*compile_cache.place(tiles, headers)))
            t1 = time.monotonic()
            if fresh:                          # first call = trace + compile
                hist.observe(t1 - t0)
                obs_profile.record_compile(
                    f"serve:{bucket}:{'+'.join(algs)}", t1 - t0)
                if obs_trace.enabled():
                    obs_trace.emit_span(
                        "compile_program", "compile", t0, t1, trace_id="",
                        bucket=bucket, algorithms=",".join(algs))
    return compile_cache.programs
