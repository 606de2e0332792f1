"""jit'd public wrappers for the Pallas kernels.

Handle host-side reflect padding (so kernel slicing is 'valid'), lane-dim
alignment to 128 multiples and [H,W] vs [N,H,W] rank.  ``interpret=None``
(the default) picks by backend: compiled Mosaic kernels on a TPU, the
Pallas interpreter anywhere else (CPU tests validate numerics there).
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pyramid import gaussian_kernel_1d, octave_increments
from repro.kernels import dispatch as _dispatch
from repro.obs import profile as _obs_profile
from repro.kernels import harris as _harris
from repro.kernels import blur as _blur
from repro.kernels import fastscore as _fast
from repro.kernels import matcher as _matcher
from repro.kernels import patches as _patches
from repro.kernels import scalespace as _scalespace

LANE = 128
# VMEM budget for the fused scale-space kernel: leave headroom below the
# TPU compiler's default 16 MiB scoped-VMEM limit per kernel (a v5e core
# has 128 MiB of VMEM) for double-buffered DMA + compiler spill
# (DESIGN.md §6).
VMEM_BUDGET_BYTES = 12 * 2 ** 20


def _interpret_default():
    return jax.default_backend() != "tpu"


def _prep(img, pad: int):
    """Reflect-pad by ``pad``; align padded W to a LANE multiple (extra
    right-pad is cropped from the output).  Returns (x [N,Hp,Wp], h, w,
    squeeze)."""
    squeeze = img.ndim == 2
    x = img[None] if squeeze else img
    n, h, w = x.shape
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad)), mode="reflect")
    extra = (-xp.shape[-1]) % LANE
    if extra:
        xp = jnp.pad(xp, ((0, 0), (0, 0), (0, extra)), mode="edge")
    return xp.astype(jnp.float32), h, w, squeeze


def _crop(out, h, w, squeeze):
    out = out[..., :h, :w]
    return out[0] if squeeze else out


@functools.partial(jax.jit, static_argnames=("k", "sigma", "shi_tomasi",
                                             "interpret"))
def harris(img, *, k: float = 0.04, sigma: float = 1.0,
           shi_tomasi: bool = False, interpret: bool = None):
    """Fused Harris / Shi-Tomasi response.  img [H,W] or [N,H,W] -> same."""
    interpret = _interpret_default() if interpret is None else interpret
    r = max(1, int(np.ceil(3.0 * sigma)))
    xp, h, w, squeeze = _prep(img, r + 1)
    wk = xp.shape[-1] - 2 * (r + 1)       # lane-aligned interior width
    out = _harris.harris_pallas(xp, k=k, sigma=sigma, shi_tomasi=shi_tomasi,
                                h=h, w=wk, interpret=interpret)
    return _crop(out, h, w, squeeze)


@functools.partial(jax.jit, static_argnames=("sigma", "interpret"))
def gaussian_blur(img, sigma: float, interpret: bool = None):
    """Separable Gaussian blur.  img [..., H, W] (leading dims flattened)."""
    interpret = _interpret_default() if interpret is None else interpret
    lead = img.shape[:-2]
    x = img.reshape((-1,) + img.shape[-2:])
    r = max(1, int(np.ceil(3.0 * sigma)))
    xp, h, w, _ = _prep(x, r)
    wk = xp.shape[-1] - 2 * r
    out = _blur.blur_pallas(xp, sigma=sigma, h=h, w=wk, interpret=interpret)
    return out[..., :h, :w].reshape(lead + img.shape[-2:])


@functools.partial(jax.jit, static_argnames=("threshold", "arc", "interpret"))
def fast_score(img, *, threshold: float = 0.15, arc: int = 9,
               interpret: bool = None):
    """FAST-N corner score.  img [H,W] or [N,H,W] -> same."""
    interpret = _interpret_default() if interpret is None else interpret
    xp, h, w, squeeze = _prep(img, 3)
    wk = xp.shape[-1] - 6
    out = _fast.fast_pallas(xp, threshold=threshold, arc=arc, h=h, w=wk,
                            interpret=interpret)
    return _crop(out, h, w, squeeze)


@functools.partial(jax.jit, static_argnames=("size", "interpret"))
def patches(img, y0, x0, *, size: int, interpret: bool = None):
    """Patches [K, size, size] of img [H, W] at start indices y0, x0 [K],
    already clipped into the tile.  ``interpret=None`` picks by backend:
    the Pallas kernel on a TPU, where XLA's gather loops over one index or
    one slice at a time; XLA's window gather anywhere else.  True or False
    runs the kernel interpreted or compiled."""
    if interpret is None:
        if _interpret_default():
            return jax.vmap(lambda y, x: jax.lax.dynamic_slice(
                img, (y, x), (size, size)))(y0, x0)
        interpret = False
    return _patches.patches_pallas(img, y0, x0, size=size,
                                   interpret=interpret)


def _scalespace_taps(scales_per_octave: int, sigma0: float):
    """Compile-time incremental taps for one octave's levels 1..n_scales-1."""
    return tuple(tuple(gaussian_kernel_1d(s).tolist())
                 for s in octave_increments(scales_per_octave, sigma0))


def scalespace_pad(scales_per_octave: int, sigma0: float = 1.6) -> int:
    """One-DMA padding: cumulative blur radius + 1 for the extrema window."""
    return sum((len(t) - 1) // 2
               for t in _scalespace_taps(scales_per_octave, sigma0)) + 1


def scalespace_vmem_bytes(h: int, w: int, scales_per_octave: int,
                          sigma0: float = 1.6) -> int:
    """Working-set estimate for the fused octave kernel: the padded input
    slab plus ~(n_levels + n_dogs + 4) live level/DoG/stat slabs (fp32),
    lane-aligned.  See DESIGN.md §6 for the budget table."""
    p = scalespace_pad(scales_per_octave, sigma0)
    wp = w + 2 * p
    wp += (-wp) % LANE
    slab = (h + 2 * p) * wp * 4
    n_levels = scales_per_octave + 3
    return (2 * n_levels + 2 + 4) * slab


def scalespace_fits_vmem(h: int, w: int, scales_per_octave: int,
                         sigma0: float = 1.6) -> bool:
    """True when a fused octave for an ``[h, w]`` tile fits the 12 MiB
    VMEM budget — the dispatcher's kernel/jnp-fallback gate."""
    return scalespace_vmem_bytes(h, w, scales_per_octave,
                                 sigma0) <= VMEM_BUDGET_BYTES


MATCH_QBLOCK = _matcher.QBLOCK


def matcher_vmem_bytes(nk: int, d: int, metric: str = "l2") -> int:
    """Working-set estimate for the matcher kernel: the VMEM-resident
    database slab + one query block + the per-chunk distance temporaries
    (Hamming: the running sum and the XOR/popcount temporaries of one
    word, each [Q, C]).  See DESIGN.md §7 for the budget table."""
    kc = min(_matcher.kchunk_for(metric), nk)
    db = nk * d * 4
    q = MATCH_QBLOCK * d * 4
    if metric == "hamming":
        tmp = MATCH_QBLOCK * kc * 4 * 4
    else:
        tmp = MATCH_QBLOCK * kc * 3 * 4 + 2 * nk * 4
    return db + q + tmp + 6 * MATCH_QBLOCK * 4


def matcher_fits_vmem(nk: int, d: int, metric: str = "l2") -> bool:
    """True when an ``[nk, d]`` descriptor database fits the matcher
    kernel's VMEM budget — the `match_best2` kernel/fallback gate."""
    return matcher_vmem_bytes(nk, d, metric) <= VMEM_BUDGET_BYTES


MATCH_PATHS = _dispatch.MATCH_PATHS


def match_path(nq: int, nk: int, d: int, *, metric: str = "l2",
               use_pallas: bool = None, backend: str = None) -> str:
    """Resolve which implementation a ``match_best2`` call of this shape
    will take — one of ``jnp_full | jnp_stream | pallas_resident |
    pallas_stream`` (`kernels/dispatch.py`).

    ``use_pallas=True`` forces a kernel: the VMEM-resident one when the
    database fits the budget, else the streaming tiled-DB kernel — there
    is no silent jnp fallback anymore.  ``use_pallas=False`` restricts to
    the jnp formulations; ``None`` (the default) lets the per-(metric,
    backend, shape-bucket) microbenchmark decide.  Tests call this to
    *assert* the dispatch decision (e.g. that a million-row
    database streams rather than falling back).
    """
    if use_pallas is True:
        if matcher_fits_vmem(nk, d, metric) and nk <= _dispatch.FULL_MAX_ROWS:
            return "pallas_resident"
        return "pallas_stream"
    return _dispatch.choose_path(metric, nq, nk, d, backend=backend,
                                 use_pallas=use_pallas)


@functools.partial(jax.jit, static_argnames=("metric", "path", "interpret"))
def _match_impl(queries, db, db_valid, *, metric: str, path: str,
                interpret: bool):
    """One matcher implementation, jit'd per (metric, path): padding and
    lane alignment happen inside the trace so callers stay shape-exact."""
    nq, nk = queries.shape[0], db.shape[0]
    if metric == "l2":
        queries = queries.astype(jnp.float32)
        db = db.astype(jnp.float32)
    if path == "jnp_full":
        return _matcher.best2_full(queries, db, db_valid, metric=metric)
    if path == "jnp_stream":
        return _matcher.best2_stream(queries, db, db_valid, metric=metric)
    if metric == "l2":
        extra = (-queries.shape[1]) % LANE     # zero-pad D to a lane multiple
        if extra:
            queries = jnp.pad(queries, ((0, 0), (0, extra)))
            db = jnp.pad(db, ((0, 0), (0, extra)))
    pad_q = (-nq) % MATCH_QBLOCK
    qp = jnp.pad(queries, ((0, pad_q), (0, 0))) if pad_q else queries
    mask = db_valid.astype(jnp.int32)
    if path == "pallas_resident":
        kernel, step = _matcher.match_pallas, _matcher.kchunk_for(metric)
    else:                                      # pallas_stream
        kernel, step = (_matcher.match_pallas_stream,
                        _matcher.kblock_for(metric))
    pad_k = (-nk) % step
    if pad_k:                                  # pad rows masked invalid
        db = jnp.pad(db, ((0, pad_k), (0, 0)))
        mask = jnp.pad(mask, (0, pad_k))
    best, second, idx = kernel(qp, db.T, mask[None, :], metric=metric,
                               interpret=interpret)
    return best[:nq], second[:nq], idx[:nq]


def match_best2(queries, db, db_valid=None, *, metric: str = "l2",
                use_pallas: bool = None, interpret: bool = None,
                path: str = None):
    """Per-query (best, second-best, argbest) over a masked descriptor DB.

    queries [Q, D], db [K, D], db_valid [K] (None = all valid).  For
    ``metric="hamming"`` both must be bit-packed uint32 word lanes
    (``descriptors.pack_bits`` layout); distances are exact int32.  For
    ``metric="l2"`` inputs are cast to fp32 and distances are *squared* L2
    (monotonic for ranking; the ratio test squares its threshold).

    Dispatch is **benchmark-gated** (`kernels/dispatch.py`): by default
    (``use_pallas=None``) a one-shot microbenchmark per (metric, backend,
    shape-bucket) — cached on disk — picks the fastest of the jnp
    formulations and (on TPU) the Pallas kernels, so a backend where one
    path regresses silently gets the fast one.  ``use_pallas=True``
    forces a kernel (resident under the VMEM budget, streaming above it
    — a million-row database streams instead of falling back);
    ``use_pallas=False`` forces jnp; ``path`` pins an exact
    implementation (one of `MATCH_PATHS`, mainly for tests/benchmarks).
    Every path computes the same distances with the same masking and
    smallest-index tie-breaks, so the choice is performance, never
    numerics (Hamming results are bit-identical across all four).

    The decision needs only shapes, so calls from inside ``jit``/``vmap``
    traces resolve at trace time and bake the chosen path into the
    compiled program.
    """
    interpret = _interpret_default() if interpret is None else interpret
    nq, nk = queries.shape[0], db.shape[0]
    if db_valid is None:
        db_valid = jnp.ones((nk,), jnp.bool_)
    if metric == "hamming":
        if queries.dtype != jnp.uint32 or db.dtype != jnp.uint32:
            raise TypeError("hamming matching needs bit-packed uint32 "
                            "descriptors (descriptors.pack_bits)")
    elif metric != "l2":
        raise ValueError(f"unknown metric {metric!r}")
    if path is None:
        path = match_path(nq, nk, queries.shape[1], metric=metric,
                          use_pallas=use_pallas)
    elif path not in MATCH_PATHS:
        raise ValueError(f"unknown path {path!r} (want one of {MATCH_PATHS})")
    prof = _obs_profile.profiler()
    if not prof.enabled:
        # hot path: zero extra work, and critically NO synchronization —
        # profiling must never change the async dispatch behavior of an
        # unprofiled run
        return _match_impl(queries, db, db_valid, metric=metric, path=path,
                           interpret=interpret)
    qb, kb, db_w = _dispatch.shape_bucket(nq, nk, queries.shape[1])
    t0 = time.monotonic()
    out = _match_impl(queries, db, db_valid, metric=metric, path=path,
                      interpret=interpret)
    try:
        jax.block_until_ready(out)             # put async work on the clock
    except Exception:  # noqa: BLE001 — tracers inside someone else's jit
        pass
    prof.record_call(f"match:{metric}:{path}:q{qb}k{kb}d{db_w}",
                     time.monotonic() - t0)
    return out


@functools.partial(jax.jit, static_argnames=("scales_per_octave",
                                             "contrast_threshold", "sigma0",
                                             "interpret"))
def scalespace_octave(base, *, scales_per_octave: int,
                      contrast_threshold: float, sigma0: float = 1.6,
                      interpret: bool = None):
    """Fused SIFT octave: (extrema response, next-octave seed level).

    ``base`` [H,W] or [N,H,W], already blurred to ``sigma0`` (octave level
    0).  One pallas_call computes the whole octave's Gaussian stack, DoG
    differences and 3x3x3 extrema in VMEM; only the response and the seed
    level are written back.
    """
    interpret = _interpret_default() if interpret is None else interpret
    taps_list = _scalespace_taps(scales_per_octave, float(sigma0))
    p = sum((len(t) - 1) // 2 for t in taps_list) + 1
    xp, h, w, squeeze = _prep(base, p)
    wk = xp.shape[-1] - 2 * p
    resp, seed = _scalespace.scalespace_pallas(
        xp, taps_list=taps_list, h=h, w=wk,
        seed_index=scales_per_octave,
        contrast_threshold=float(contrast_threshold), interpret=interpret)
    return _crop(resp, h, w, squeeze), _crop(seed, h, w, squeeze)
