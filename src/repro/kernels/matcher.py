"""Tiled brute-force descriptor matcher kernels (popcount-Hamming + L2).

The matching stage pairs every query descriptor against a scene's database
and keeps the best and second-best distances (the Lowe ratio test needs
both).  A naive lowering materializes the full [Q, K] distance matrix in
HBM — for binary descriptors it is even worse, because the obvious jnp
formulation unpacks 256-bit descriptors into 256 bools (32x the traffic).

Two Pallas kernels cover the database-size spectrum:

* **Resident** (`match_pallas`): the whole database stays VMEM-resident
  across the query grid; each program owns one ``QBLOCK``-query block and
  scans the database in chunks that never leave VMEM (a rolled loop).  Cheapest when the
  database fits the VMEM budget (``ops.matcher_fits_vmem``).
* **Streaming** (`match_pallas_stream`): a second *database* grid
  dimension tiles the database into ``KBLOCK``-row chunks that Pallas
  pipelines HBM→VMEM with double-buffered DMA; the (best, second-best,
  argbest) registers live in the revisited output block, carried in VMEM
  across the whole database sweep.  One query batch scans millions of
  descriptors without ever holding more than two chunks on-chip.

Distance formulations (identical across kernels and jnp paths):

* **Hamming (BRIEF/ORB)**: descriptors stay bit-packed as uint32 lanes
  (256 bits = 8 words); per-word XOR + SWAR popcount (the shift-mask-add
  reduction — 5 integer VPU ops per word) summed over words.  Distances
  are exact int32, so kernel/oracle/fallback agree *bit-identically*.
* **L2 (SIFT/SURF)**: the ``|q|^2 + |k|^2 - 2 q.k`` expansion; the q.k
  block is one MXU ``dot_general`` per chunk at HIGHEST precision
  (fp32 inputs, fp32 accumulation — see `_chunk_dist`).  The
  ``|q|^2`` term is constant per query row, so the scan ranks on the
  partial ``|k|^2 - 2 q.k`` and adds ``|q|^2`` once at the end — no
  per-chunk re-broadcast of the query norms over the [Q, C] block.

The jnp twins — `best2_full` (one [Q, K] block) and `best2_stream`
(``lax.scan`` over database chunks, the same carried-register merge the
streaming kernel runs) — are real production paths, not just fallbacks:
`kernels/dispatch.py` microbenchmarks them against the kernels per
(metric, backend, shape-bucket) and `ops.match_best2` routes each call
site to whichever wins on the current host.

Invalid database slots (validity masks come from capacity-K extraction)
are forced to a BIG distance before the running update; ties are broken
toward the smallest database index (first occurrence of the chunk
minimum + a strictly-less merge), so matches are deterministic and partition-invariant
— in every path, streaming included (chunks merge in database order).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

QBLOCK = 128          # queries per program (VPU sublane-friendly)
BIG_HAMMING = 1 << 30     # > any packed-bit distance; < int32 max


def kchunk_for(metric: str) -> int:
    """Database rows per VMEM-resident chunk: the width of the [Q, C]
    distance block one loop step scores.  Hamming builds it from W
    per-word XOR/popcount passes (~12 VPU ops per element per word), so
    it chunks 4x finer than L2, whose block comes off the MXU."""
    return 256 if metric == "hamming" else 1024


def kblock_for(metric: str) -> int:
    """Database rows per streamed chunk (the streaming kernel's DB grid
    tile and `best2_stream`'s scan step).  Wider than `kchunk_for` — a
    streamed chunk is also the DMA transfer unit, so it must amortize
    the HBM round-trip, not just bound the VMEM temporary."""
    return 512 if metric == "hamming" else 2048


def big_for(metric: str):
    """The masked/initial distance: larger than any real distance, exact
    in the metric's dtype (int32 Hamming / fp32 inf for L2)."""
    return jnp.int32(BIG_HAMMING) if metric == "hamming" \
        else jnp.float32(jnp.inf)


def popcount32(x):
    """Per-word population count of a uint32 array (SWAR bit-slicing)."""
    x = x - ((x >> 1) & 0x55555555)
    x = (x & 0x33333333) + ((x >> 2) & 0x33333333)
    x = (x + (x >> 4)) & 0x0F0F0F0F
    return (x * 0x01010101) >> 24          # byte-sum via overflowing multiply


def _chunk_dist(q, ct, m, metric, big, dn=None):
    """Distances of one DB chunk: [Q, C], invalid slots forced to big.

    ``ct`` is the chunk *transposed*, [D, C], and ``m`` its [1, C]
    validity row, so database rows run along the lanes: the L2 ``q.k``
    block is a plain [Q, D] x [D, C] MXU matmul, ``|k|^2`` reduces over
    sublanes into a lane row, and Hamming XORs one [Q, 1] query word
    column against one [1, C] database word row per word — no [Q, C, W]
    intermediate with a W-word lane axis.  L2 omits the |q|^2 term
    (constant per row — callers add it once at the end of the scan);
    ``dn`` lets callers pass a precomputed [1, C] |k|^2.  The matmul runs
    at HIGHEST precision: a TPU's default f32 matmul rounds its inputs to
    bfloat16, which would make the TPU's distances (and so its matches)
    depend on the path."""
    if metric == "hamming":
        d = sum(popcount32(q[:, w:w + 1] ^ ct[w:w + 1, :]).astype(jnp.int32)
                for w in range(q.shape[1]))
    else:
        dot = jax.lax.dot_general(q, ct, (((1,), (0,)), ((), ())),
                                  precision=jax.lax.Precision.HIGHEST,
                                  preferred_element_type=jnp.float32)
        dn = jnp.sum(ct * ct, axis=0, keepdims=True) if dn is None else dn
        d = dn - 2.0 * dot
    return jnp.where(m != 0, d, big)


def _chunk_best2(d, start, big):
    """Best/second/argbest of one [Q, C] distance chunk as [Q, 1] columns;
    indices global.  The argbest is the smallest column holding the
    minimum (argmin's first occurrence) as a min over an iota: Mosaic
    lowers argmin only for float32, and Hamming distances are int32."""
    best = jnp.min(d, axis=1, keepdims=True)
    cols = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    arg = jnp.min(jnp.where(d == best, cols, d.shape[1]), axis=1,
                  keepdims=True)
    second = jnp.min(jnp.where(cols == arg, big, d), axis=1, keepdims=True)
    return best, second, arg + start


def _merge_best2(carry, chunk):
    """Merge a chunk's (best, second, idx) into the carried registers.
    Strictly-less ``take`` keeps the earlier (smaller-index) winner on
    ties, so the merge order — database order — fixes the tie-break."""
    best, second, bidx = carry
    cb, cs, ci = chunk
    take = cb < best
    second = jnp.where(take, jnp.minimum(best, cs), jnp.minimum(second, cb))
    bidx = jnp.where(take, ci, bidx)
    best = jnp.where(take, cb, best)
    return best, second, bidx


def _l2_qnorm(q, best, second):
    """Fold the per-query |q|^2 back into the scanned partial [Q, 1]
    distances (masked slots are +inf, which absorbs the add)."""
    qn = jnp.sum(q * q, axis=-1, keepdims=True)
    return best + qn, second + qn


def _init_best2(nq: int, big):
    return (jnp.full((nq, 1), big), jnp.full((nq, 1), big),
            jnp.zeros((nq, 1), jnp.int32))


def _columns_to_vectors(best, second, bidx):
    return best[:, 0], second[:, 0], bidx[:, 0]


def best2_full(q, db, db_valid, *, metric: str):
    """One-block best/second-best: the whole [Q, K] distance matrix in a
    single chunk.  On hosts where materializing the matrix is cheap (CPU
    XLA; small K) this is the fastest formulation — the dispatcher picks
    it per backend (`kernels/dispatch.py`).  q [Q, D], db [K, D],
    db_valid [K] -> (best [Q], second [Q], idx [Q] int32)."""
    big = big_for(metric)
    d = _chunk_dist(q, db.T, db_valid.astype(jnp.int32)[None, :], metric,
                    big)
    best, second, bidx = _chunk_best2(d, 0, big)
    if metric == "l2":
        best, second = _l2_qnorm(q, best, second)
    return _columns_to_vectors(best, second, bidx)


def best2_stream(q, db, db_valid, *, metric: str, kchunk: int = None):
    """Rolled streaming scan: ``lax.scan`` over [K/C, D, C]-chunked
    (transposed) database slabs with carried (best, second, argbest)
    registers — the jnp twin of the streaming Pallas kernel, and the path
    that lets one query batch scan millions of descriptors on any backend
    (constant working set, no [Q, K] materialization, trace size
    independent of K).

    The database is zero-padded to a chunk multiple (padding rows are
    masked invalid), so tail chunks need no special casing.
    """
    nq, nk = q.shape[0], db.shape[0]
    kchunk = kblock_for(metric) if kchunk is None else kchunk
    big = big_for(metric)
    if metric not in ("hamming", "l2"):
        raise ValueError(f"unknown metric {metric!r}")
    db_valid = db_valid.astype(jnp.int32)
    pad = (-nk) % kchunk
    if pad:
        db = jnp.pad(db, ((0, pad), (0, 0)))
        db_valid = jnp.pad(db_valid, (0, pad))
    n_chunks = (nk + pad) // kchunk
    dbc = db.reshape(n_chunks, kchunk, db.shape[1]).transpose(0, 2, 1)
    mc = db_valid.reshape(n_chunks, 1, kchunk)

    def step(carry, xs):
        ct, m, start = xs
        d = _chunk_dist(q, ct, m, metric, big)
        return _merge_best2(carry, _chunk_best2(d, start, big)), None

    starts = jnp.arange(n_chunks, dtype=jnp.int32) * kchunk
    (best, second, bidx), _ = jax.lax.scan(step, _init_best2(nq, big),
                                           (dbc, mc, starts))
    if metric == "l2":
        best, second = _l2_qnorm(q, best, second)
    return _columns_to_vectors(best, second, bidx)


# ---- Pallas kernels ---------------------------------------------------------
#
# Both kernels take the database transposed, [D, K], with its validity as
# a [1, K] int32 row, and write (best, second, idx) as [NQ, 1] columns in
# [QBLOCK, 1] blocks: the lane reductions over a [QBLOCK, C] distance
# block leave one value per sublane row, which is exactly that layout.

def _best2_out_shapes(nq: int, dist_dt):
    return [jax.ShapeDtypeStruct((nq, 1), dist_dt),
            jax.ShapeDtypeStruct((nq, 1), dist_dt),
            jax.ShapeDtypeStruct((nq, 1), jnp.int32)]


# ---- resident kernel (whole DB in VMEM across the query grid) --------------

def match_kernel(q_ref, dbt_ref, mask_ref, best_ref, sec_ref, idx_ref, *,
                 metric: str, kchunk: int):
    """q_ref [QBLOCK, D]; dbt_ref [D, K] (whole DB, VMEM-resident across
    the query grid; K a ``kchunk`` multiple); mask_ref [1, K] int32;
    outputs [QBLOCK, 1] each.  A rolled loop scans the resident DB in
    ``kchunk``-column slices, so the program's size does not grow with
    K."""
    q = q_ref[...]
    big = big_for(metric)

    def chunk(c, carry):
        start = pl.multiple_of(c * kchunk, kchunk)
        d = _chunk_dist(q, dbt_ref[:, pl.ds(start, kchunk)],
                        mask_ref[:, pl.ds(start, kchunk)], metric, big)
        return _merge_best2(carry, _chunk_best2(d, start, big))

    b, s, i = jax.lax.fori_loop(0, dbt_ref.shape[1] // kchunk, chunk,
                                _init_best2(q.shape[0], big))
    if metric == "l2":
        b, s = _l2_qnorm(q, b, s)
    best_ref[...] = b
    sec_ref[...] = s
    idx_ref[...] = i


def match_pallas(q, dbt, db_mask, *, metric: str, interpret: bool,
                 kchunk: int = None):
    """q [NQ, D] (NQ a QBLOCK multiple), dbt [D, NK] (NK a ``kchunk``
    multiple — pad rows masked invalid), db_mask [1, NK] int32 ->
    (best [NQ], second [NQ], idx [NQ])."""
    nq, d = q.shape
    nk = dbt.shape[1]
    kchunk = kchunk_for(metric) if kchunk is None else kchunk
    dist_dt = jnp.int32 if metric == "hamming" else jnp.float32
    kern = functools.partial(match_kernel, metric=metric, kchunk=kchunk)
    outs = pl.pallas_call(
        kern,
        grid=(nq // QBLOCK,),
        in_specs=[pl.BlockSpec((QBLOCK, d), lambda i: (i, 0)),
                  pl.BlockSpec((d, nk), lambda i: (0, 0)),
                  pl.BlockSpec((1, nk), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((QBLOCK, 1), lambda i: (i, 0))] * 3,
        out_shape=_best2_out_shapes(nq, dist_dt),
        interpret=interpret,
        name=f"match_resident_{metric}",
    )(q, dbt, db_mask)
    return tuple(o.reshape(-1) for o in outs)


# ---- streaming kernel (tiled DB grid, carried registers) -------------------

def stream_kernel(q_ref, dbt_ref, mask_ref, best_ref, sec_ref, idx_ref, *,
                  metric: str, kblock: int, n_kblocks: int):
    """One (query-block, DB-chunk) grid step of the streaming matcher.

    The DB axis is the *minor* grid dimension, so for a fixed query block
    the output refs map to the same [QBLOCK, 1] block across every DB
    step — Pallas keeps them VMEM-resident between revisits, making them
    the carried (best, second, argbest) registers; they are initialized
    at the first chunk and written back to HBM only after the last.
    Meanwhile ``dbt_ref``/``mask_ref`` advance along the DB grid, which
    Pallas pipelines as double-buffered HBM→VMEM DMA (chunk k+1 streams
    in while chunk k is scored).  L2 scans the qn-free partial distance
    and folds |q|^2 in at the final chunk (see module docstring)."""
    ki = pl.program_id(1)
    big = big_for(metric)
    dt = best_ref.dtype

    @pl.when(ki == 0)
    def _init():
        best_ref[...] = jnp.full(best_ref.shape, big, dt)
        sec_ref[...] = jnp.full(sec_ref.shape, big, dt)
        idx_ref[...] = jnp.zeros(idx_ref.shape, jnp.int32)

    q = q_ref[...]
    d = _chunk_dist(q, dbt_ref[...], mask_ref[...], metric, big)
    best, second, bidx = _merge_best2(
        (best_ref[...], sec_ref[...], idx_ref[...]),
        _chunk_best2(d, ki * kblock, big))          # global indices
    idx_ref[...] = bidx
    if metric == "l2":
        last = ki == n_kblocks - 1
        best_q, second_q = _l2_qnorm(q, best, second)
        best_ref[...] = jnp.where(last, best_q, best)
        sec_ref[...] = jnp.where(last, second_q, second)
    else:
        best_ref[...] = best
        sec_ref[...] = second


def match_pallas_stream(q, dbt, db_mask, *, metric: str, interpret: bool,
                        kblock: int = None):
    """Streaming/tiled-database matcher: q [NQ, D] (NQ a QBLOCK multiple),
    dbt [D, NK] (NK a KBLOCK multiple — pad rows masked invalid),
    db_mask [1, NK] int32 -> (best [NQ], second [NQ], idx [NQ]).

    VMEM working set is ~2 DB chunks + 1 query block + the chunk
    temporaries, independent of NK — the database streams from HBM, so
    NK is bounded by HBM, not by the 12 MiB VMEM budget that gates the
    resident kernel."""
    nq, d = q.shape
    nk = dbt.shape[1]
    kblock = kblock_for(metric) if kblock is None else kblock
    dist_dt = jnp.int32 if metric == "hamming" else jnp.float32
    grid = (nq // QBLOCK, nk // kblock)
    kern = functools.partial(stream_kernel, metric=metric, kblock=kblock,
                             n_kblocks=grid[1])
    outs = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((QBLOCK, d), lambda i, k: (i, 0)),
                  pl.BlockSpec((d, kblock), lambda i, k: (0, k)),
                  pl.BlockSpec((1, kblock), lambda i, k: (0, k))],
        out_specs=[pl.BlockSpec((QBLOCK, 1), lambda i, k: (i, 0))] * 3,
        out_shape=_best2_out_shapes(nq, dist_dt),
        interpret=interpret,
        name=f"match_stream_{metric}",
    )(q, dbt, db_mask)
    return tuple(o.reshape(-1) for o in outs)
