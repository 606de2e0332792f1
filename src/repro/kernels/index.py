"""Approximate pre-filter indexes for the matcher: multi-probe LSH over
packed Hamming bits (BRIEF/ORB) and a small k-means vocabulary with
inverted lists for L2 (SIFT/SURF).

Brute force scans every database row per query; at fleet scale (the
million-descriptor databases `ops.match_best2` streams) most of that work
scores rows that were never going to win.  These indexes cut the scored
set to a few hundred *candidates* per query, then re-rank the candidates
with the **exact** metric — so an approximate match is always a real
(best, second-best, argbest) over the candidate set, with the same
distances, masking, and smallest-index tie-breaks as the exact paths.
The only approximation is recall: a query whose true winner fell outside
the candidate set mismatches.  On matching workloads (near-duplicate
descriptors at small distance) recall at the default knobs is >0.95 of
the exact pipeline's accepted matches (`tests/test_index.py`); the
``probes`` knob trades recall back
against latency.

* :class:`LshIndex` — ``n_tables`` hash tables, each hashing ``n_bits``
  randomly-sampled bit positions of the packed descriptor into a bucket.
  Multi-probe: besides the query's own bucket, the ``probes-1``
  single-bit-flip neighbor buckets are scanned in each table, so a
  near-duplicate that disagrees on one sampled bit still collides —
  the standard trick to hold recall with far fewer tables.
* :class:`KMeansIndex` — a small Lloyd-iteration vocabulary; each valid
  database row lives in exactly one centroid's inverted list, queries
  scan the ``probes`` nearest centroids' lists.

Index *construction* is host-side numpy (it happens once per database);
the *query* path (`search`) is pure jnp on fixed-shape candidate arrays,
so it jits.  `core/matching.match_pair(mode="approx")` wires these under
the mutual-NN + ratio pipeline.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import matcher as _matcher

_RERANK_CHUNK = 128     # candidate columns scored per slab in rerank


def default_bits(nk: int) -> int:
    """Hash width for an ``nk``-row database: ~log2(nk) keeps expected
    bucket occupancy O(1) without shredding recall (clamped to [6, 16])."""
    return int(np.clip(int(np.ceil(np.log2(max(nk, 2)))), 6, 16))


def rerank_exact(q, db, db_valid, cand, *, metric: str):
    """Exact best/second/argbest over per-query candidate sets.

    q [Q, D], db [K, D], db_valid [K], cand [Q, C] int32 global database
    indices (< 0 = empty slot) -> (best [Q], second [Q], idx [Q] int32).
    Candidates are sorted per row so duplicates (the same row surfaced by
    several tables/probes) can be masked — without dedup a duplicated
    best would masquerade as the second-best and wreck the ratio test —
    and so argmin's first-occurrence keeps the exact paths' smallest-
    index tie-break.  Distances are computed with the exact metric in
    `_RERANK_CHUNK`-column slabs (bounded temporaries at any C).
    """
    big = _matcher.big_for(metric)
    nq, nc = cand.shape
    cand = jnp.sort(cand, axis=1)                      # -1s first, dups adjacent
    dup = jnp.concatenate(
        [jnp.zeros((nq, 1), jnp.bool_), cand[:, 1:] == cand[:, :-1]], axis=1)
    ok = (cand >= 0) & ~dup & (db_valid[jnp.clip(cand, 0)] != 0)
    safe = jnp.clip(cand, 0)
    best = jnp.full((nq,), big)
    second = jnp.full((nq,), big)
    bidx = jnp.zeros((nq,), jnp.int32)
    for s in range(0, nc, _RERANK_CHUNK):
        csl = safe[:, s:s + _RERANK_CHUNK]
        rows = db[csl]                                 # [Q, c, D]
        if metric == "hamming":
            d = _matcher.popcount32(q[:, None, :] ^ rows) \
                .astype(jnp.int32).sum(axis=-1)
        else:
            diff = q[:, None, :].astype(jnp.float32) - rows.astype(jnp.float32)
            d = jnp.sum(diff * diff, axis=-1)
        d = jnp.where(ok[:, s:s + _RERANK_CHUNK], d, big)
        arg = jnp.argmin(d, axis=1).astype(jnp.int32)
        cb = jnp.min(d, axis=1)
        cols = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
        cs = jnp.min(jnp.where(cols == arg[:, None], big, d), axis=1)
        ci = jnp.take_along_axis(csl, arg[:, None], axis=1)[:, 0]
        best, second, bidx = _matcher._merge_best2(
            (best, second, bidx), (cb, cs, ci.astype(jnp.int32)))
    return best, second, bidx


class LshIndex:
    """Multi-probe LSH over bit-packed (uint32-word) binary descriptors.

    Build is numpy host-side; :meth:`search` is jnp/jit-able.  Inverted
    lists have fixed capacity ``bucket_cap``; overflowing rows are
    dropped from that table (but usually survive in another — the
    drop count is exposed as ``overflow``).
    """

    metric = "hamming"

    def __init__(self, db, db_valid=None, *, n_tables: int = 8,
                 n_bits: Optional[int] = None,
                 bucket_cap: Optional[int] = None,
                 probes: Optional[int] = None, seed: int = 0):
        db = np.asarray(db)
        if db.dtype != np.uint32:
            raise TypeError("LshIndex needs bit-packed uint32 descriptors "
                            "(descriptors.pack_bits layout)")
        nk, words = db.shape
        valid = (np.ones(nk, bool) if db_valid is None
                 else np.asarray(db_valid).astype(bool))
        self.n_tables = int(n_tables)
        self.n_bits = default_bits(nk) if n_bits is None else int(n_bits)
        # default probes: the query bucket + every single-bit flip
        self.probes = self.n_bits + 1 if probes is None else int(probes)
        if bucket_cap is None:
            # ~4x the expected uniform occupancy, floor 8: skewed buckets
            # keep their head entries, the tail is what overflow drops
            bucket_cap = max(8, int(4 * np.ceil(nk / 2 ** self.n_bits)))
        self.bucket_cap = int(bucket_cap)
        rng = np.random.RandomState(seed)
        # sampled bit positions: (table, bit) -> distinct bits per table
        pos = np.stack([rng.choice(words * 32, self.n_bits, replace=False)
                        for _ in range(self.n_tables)])
        self._word = (pos // 32).astype(np.int32)
        self._shift = (pos % 32).astype(np.uint32)
        codes = self._codes_np(db)                     # [T, K]
        lists = np.full((self.n_tables, 2 ** self.n_bits, self.bucket_cap),
                        -1, np.int32)
        self.overflow = 0
        rows = np.nonzero(valid)[0]
        for t in range(self.n_tables):
            # vectorized fill in db order (deterministic): stable-sort by
            # bucket, rank within bucket, keep ranks under capacity
            c = codes[t, rows]
            order = np.argsort(c, kind="stable")
            cs, rs = c[order], rows[order]
            first = np.concatenate([[True], cs[1:] != cs[:-1]])
            pos_in = np.arange(len(cs)) - \
                np.maximum.accumulate(np.where(first, np.arange(len(cs)), 0))
            keep = pos_in < self.bucket_cap
            self.overflow += int((~keep).sum())
            lists[t, cs[keep], pos_in[keep]] = rs[keep]
        self.n_rows = int(nk)
        self._db = jnp.asarray(db)
        self._valid = jnp.asarray(valid)
        self._lists = jnp.asarray(lists)
        self._wordj = jnp.asarray(self._word)
        self._shiftj = jnp.asarray(self._shift)

    def _codes_np(self, x: np.ndarray) -> np.ndarray:
        bits = (x[:, self._word] >> self._shift) & np.uint32(1)   # [N, T, B]
        weights = (np.uint32(1) << np.arange(self.n_bits, dtype=np.uint32))
        return bits.astype(np.uint32).dot(weights).T.astype(np.int32)

    def _codes(self, q) -> jnp.ndarray:
        bits = (q[:, self._wordj] >> self._shiftj) & jnp.uint32(1)
        weights = (jnp.uint32(1)
                   << jnp.arange(self.n_bits, dtype=jnp.uint32))
        return jnp.sum(bits * weights, axis=-1, dtype=jnp.uint32) \
            .astype(jnp.int32).T                       # [T, Q]

    def candidates(self, q, probes: Optional[int] = None) -> jnp.ndarray:
        """Candidate database indices per query: [Q, T*probes*cap] int32,
        -1 for empty slots; duplicates possible (rerank dedups)."""
        probes = self.probes if probes is None else int(probes)
        probes = min(probes, self.n_bits + 1)
        codes = self._codes(q)                          # [T, Q]
        flips = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             (1 << jnp.arange(probes - 1, dtype=jnp.int32))])
        probed = codes[:, :, None] ^ flips[None, None, :]   # [T, Q, P]
        tbl = jnp.arange(self.n_tables, dtype=jnp.int32)[:, None, None]
        cand = self._lists[tbl, probed]                 # [T, Q, P, cap]
        return jnp.moveaxis(cand, 0, 1).reshape(q.shape[0], -1)

    def search(self, q, probes: Optional[int] = None):
        """Approximate (best, second, idx) for q [Q, W] uint32: gather
        candidates from the probed buckets, exact-Hamming re-rank."""
        cand = self.candidates(q, probes)
        return rerank_exact(q, self._db, self._valid, cand,
                            metric=self.metric)


class KMeansIndex:
    """k-means vocabulary + inverted lists for float (L2) descriptors.

    A few Lloyd iterations over the valid rows build ``n_clusters``
    centroids; every row lives in exactly one centroid's fixed-capacity
    list (lists are disjoint, so no dedup pressure in rerank).  Queries
    scan the ``probes`` nearest centroids' lists.
    """

    metric = "l2"

    def __init__(self, db, db_valid=None, *, n_clusters: Optional[int] = None,
                 iters: int = 8, bucket_cap: Optional[int] = None,
                 probes: int = 8, seed: int = 0):
        db = np.asarray(db, np.float32)
        nk, d = db.shape
        valid = (np.ones(nk, bool) if db_valid is None
                 else np.asarray(db_valid).astype(bool))
        rows = np.nonzero(valid)[0]
        pts = db[rows] if len(rows) else db[:1]
        if n_clusters is None:
            n_clusters = int(np.clip(int(np.sqrt(max(len(pts), 1))), 4, 1024))
        self.n_clusters = min(int(n_clusters), max(len(pts), 1))
        rng = np.random.RandomState(seed)
        cent = pts[rng.choice(len(pts), self.n_clusters,
                              replace=len(pts) < self.n_clusters)].copy()
        for _ in range(int(iters)):
            d2 = (np.sum(pts * pts, 1)[:, None]
                  + np.sum(cent * cent, 1)[None, :] - 2.0 * pts @ cent.T)
            assign = np.argmin(d2, axis=1)
            for c in range(self.n_clusters):
                m = assign == c
                if m.any():
                    cent[c] = pts[m].mean(axis=0)
        d2 = (np.sum(pts * pts, 1)[:, None]
              + np.sum(cent * cent, 1)[None, :] - 2.0 * pts @ cent.T)
        assign = np.argmin(d2, axis=1)
        self.probes = min(int(probes), self.n_clusters)
        if bucket_cap is None:
            counts = np.bincount(assign, minlength=self.n_clusters)
            bucket_cap = max(8, int(counts.max())) if len(pts) else 8
        self.bucket_cap = int(bucket_cap)
        lists = np.full((self.n_clusters, self.bucket_cap), -1, np.int32)
        fill = np.zeros(self.n_clusters, np.int32)
        self.overflow = 0
        for i, c in zip(rows, assign):                 # db order: deterministic
            if fill[c] < self.bucket_cap:
                lists[c, fill[c]] = i
                fill[c] += 1
            else:
                self.overflow += 1
        self.n_rows = int(nk)
        self._db = jnp.asarray(db)
        self._valid = jnp.asarray(valid)
        self._cent = jnp.asarray(cent)
        self._lists = jnp.asarray(lists)

    def candidates(self, q, probes: Optional[int] = None) -> jnp.ndarray:
        """Candidate database indices per query: [Q, probes*cap] int32,
        -1 for empty slots (lists are disjoint — no duplicates)."""
        probes = self.probes if probes is None else \
            min(int(probes), self.n_clusters)
        q = q.astype(jnp.float32)
        d2 = (jnp.sum(q * q, 1)[:, None]
              + jnp.sum(self._cent * self._cent, 1)[None, :]
              - 2.0 * q @ self._cent.T)
        _, near = jax.lax.top_k(-d2, probes)            # [Q, probes]
        return self._lists[near].reshape(q.shape[0], -1)

    def search(self, q, probes: Optional[int] = None):
        """Approximate (best, second, idx) for q [Q, D] float: exact-L2
        re-rank over the ``probes`` nearest centroids' inverted lists."""
        cand = self.candidates(q, probes)
        return rerank_exact(q.astype(jnp.float32), self._db, self._valid,
                            cand, metric=self.metric)


def build_index(db, db_valid=None, *, metric: Optional[str] = None,
                **knobs):
    """Index factory: packed uint32 descriptors (or ``metric="hamming"``)
    get an :class:`LshIndex`, float descriptors a :class:`KMeansIndex`.
    ``knobs`` forward to the index constructor."""
    if metric is None:
        metric = "hamming" if np.asarray(db).dtype == np.uint32 else "l2"
    if metric == "hamming":
        return LshIndex(db, db_valid, **knobs)
    if metric == "l2":
        return KMeansIndex(db, db_valid, **knobs)
    raise ValueError(f"unknown metric {metric!r}")
