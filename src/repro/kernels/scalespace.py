"""Fused SIFT scale-space octave kernel: one DMA per tile.

The level-by-level path DMAs every Gaussian level, every DoG difference and
the 26-neighbour extrema stack through HBM — (n_scales + n_scales-1 + 26)
round-trips per octave for the costliest algorithm in the paper's Table 1.
This kernel does ONE: the padded tile is DMA'd to VMEM and the whole
octave — incremental Gaussian stack, DoG differences, and the 3x3x3
DoG-extrema response — is computed on VMEM values inside a single
``pallas_call``.  Only two maps leave VMEM: the octave's extrema response
and the seed level (total sigma ``2*sigma0``) that the caller downsamples
to start the next octave.

Incremental-sigma taps are compile-time constants (the semigroup split of
the octave's sigmas is static), so every separable pass unrolls into
fused multiply-adds, mirroring ``harris_kernel``.

Convention: the caller reflect-pads the tile ONCE by the cumulative blur
radius (+1 for the extrema window); every level is then a valid
convolution with a shrinking margin.  ``kernels/ref.py::scalespace_octave``
is the oracle with the same convention; the production jnp path pads per
level instead, so the two agree only beyond the cumulative-radius band
(DESIGN.md §6).

Grid: one program per tile.  VMEM working set is ~(n_scales + 4) padded
slabs; the ops.py wrapper checks it against a 12 MiB budget (under the
TPU compiler's default 16 MiB scoped-VMEM limit) and the dispatcher takes
the streaming jnp path for oversized tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _blur_valid(x, taps, out_h: int, out_w: int):
    """Separable valid blur of the VMEM value x -> (out_h, out_w)."""
    r = (len(taps) - 1) // 2
    tmp = sum(float(taps[j]) * x[:, j:j + out_w] for j in range(2 * r + 1))
    return sum(float(taps[i]) * tmp[i:i + out_h, :] for i in range(2 * r + 1))


def _win3x3(d, h: int, w: int):
    """d: margin-1 slab (h+2, w+2) -> (full9_max, full9_min, ring8_max,
    ring8_min), each (h, w), via separable shifted-max chains."""
    col = lambda x: (jnp.maximum(jnp.maximum(d[:, 0:w], d[:, 1:w + 1]),
                                 d[:, 2:w + 2]),
                     jnp.minimum(jnp.minimum(d[:, 0:w], d[:, 1:w + 1]),
                                 d[:, 2:w + 2]))
    h3mx, h3mn = col(d)
    lrmx = jnp.maximum(d[:, 0:w], d[:, 2:w + 2])
    lrmn = jnp.minimum(d[:, 0:w], d[:, 2:w + 2])
    full9_max = jnp.maximum(jnp.maximum(h3mx[0:h], h3mx[1:h + 1]),
                            h3mx[2:h + 2])
    full9_min = jnp.minimum(jnp.minimum(h3mn[0:h], h3mn[1:h + 1]),
                            h3mn[2:h + 2])
    ring8_max = jnp.maximum(jnp.maximum(h3mx[0:h], h3mx[2:h + 2]),
                            lrmx[1:h + 1])
    ring8_min = jnp.minimum(jnp.minimum(h3mn[0:h], h3mn[2:h + 2]),
                            lrmn[1:h + 1])
    return full9_max, full9_min, ring8_max, ring8_min


def scalespace_kernel(x_ref, resp_ref, seed_ref, *, taps_list, h: int,
                      w: int, seed_index: int, contrast_threshold: float):
    """x_ref: [1, h + 2P, w + 2P] with P = sum(blur radii) + 1 — the
    octave's level 0 (sigma0), pre-padded.  resp_ref/seed_ref: [1, h, w]."""
    margin = sum((len(t) - 1) // 2 for t in taps_list) + 1
    prev = x_ref[0]
    dogs = []                                    # (slab, margin) pairs
    for s, taps in enumerate(taps_list, start=1):
        r = (len(taps) - 1) // 2
        m = margin - r
        eh, ew = h + 2 * m, w + 2 * m
        cur = _blur_valid(prev, taps, eh, ew)
        dogs.append((cur - prev[r:r + eh, r:r + ew], m))
        if s == seed_index:
            seed_ref[0] = cur[m:m + h, m:m + w]
        prev, margin = cur, m
    # crop every DoG slab to margin 1 and take 3x3 window stats
    stats, mids = [], []
    for d, m in dogs:
        c = m - 1
        stats.append(_win3x3(d[c:c + h + 2, c:c + w + 2], h, w))
        mids.append(d[m:m + h, m:m + w])
    resp = jnp.zeros((h, w), jnp.float32)
    for s in range(1, len(dogs) - 1):
        below_mx, below_mn, _, _ = stats[s - 1]
        above_mx, above_mn, _, _ = stats[s + 1]
        _, _, ring_mx, ring_mn = stats[s]
        mid = mids[s]
        neigh_max = jnp.maximum(jnp.maximum(below_mx, above_mx), ring_mx)
        neigh_min = jnp.minimum(jnp.minimum(below_mn, above_mn), ring_mn)
        is_ext = (mid > neigh_max) | (mid < neigh_min)
        r_s = jnp.where(is_ext & (jnp.abs(mid) > contrast_threshold),
                        jnp.abs(mid), 0.0)
        resp = jnp.maximum(resp, r_s)
    resp_ref[0] = resp


def scalespace_pallas(x_padded, *, taps_list, h: int, w: int,
                      seed_index: int, contrast_threshold: float,
                      interpret: bool):
    """x_padded: [n, h+2P, w+2P] -> (resp [n,h,w], seed [n,h,w])."""
    n, hp, wp = x_padded.shape
    kern = functools.partial(
        scalespace_kernel, taps_list=taps_list, h=h, w=w,
        seed_index=seed_index, contrast_threshold=contrast_threshold)
    return pl.pallas_call(
        kern,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, hp, wp), lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((1, h, w), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, h, w), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, h, w), jnp.float32),
                   jax.ShapeDtypeStruct((n, h, w), jnp.float32)],
        interpret=interpret,
        name="scalespace_octave",
    )(x_padded)
