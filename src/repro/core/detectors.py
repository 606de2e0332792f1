"""Corner / interest-point detectors: Harris, Shi-Tomasi, FAST, plus the
SIFT DoG-extrema and SURF fast-Hessian detection maps.

Each detector returns a dense per-pixel *response map*; NMS + capacity-K
selection (``repro.core.nms``) turns maps into keypoints.  Dense maps are
what make the TPU adaptation work: counts (paper Table 2) are exact even
when the keypoint list is capacity-truncated.

Harris / Shi-Tomasi / FAST response hot-loops have Pallas TPU kernels in
``repro.kernels`` (``use_pallas=True``); the jnp implementations here are
the oracles they are tested against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pyramid import (
    blur_separable, blur_separable_seed, sobel_gradients, gaussian_pyramid,
    dog_pyramid, downsample2, fused_octave_response, integral_image, box_sum,
)


# ---------------------------------------------------------------------------
# structure tensor: Harris & Shi-Tomasi
# ---------------------------------------------------------------------------
def structure_tensor(img, sigma: float = 1.0):
    gx, gy = sobel_gradients(img)
    ixx = blur_separable(gx * gx, sigma)
    iyy = blur_separable(gy * gy, sigma)
    ixy = blur_separable(gx * gy, sigma)
    return ixx, iyy, ixy


def harris_response(img, k: float = 0.04, sigma: float = 1.0,
                    use_pallas: bool = False):
    """R = det(M) - k * trace(M)^2  (paper's Harris mapper, steps 2-3)."""
    if use_pallas:
        from repro.kernels.ops import harris as _pallas
        return _pallas(img, k=k, sigma=sigma, shi_tomasi=False)
    ixx, iyy, ixy = structure_tensor(img, sigma)
    det = ixx * iyy - ixy * ixy
    tr = ixx + iyy
    return det - k * tr * tr


def shi_tomasi_response(img, sigma: float = 1.0, use_pallas: bool = False):
    """min-eigenvalue response: lambda_min of the structure tensor."""
    if use_pallas:
        from repro.kernels.ops import harris as _pallas
        return _pallas(img, k=0.0, sigma=sigma, shi_tomasi=True)
    ixx, iyy, ixy = structure_tensor(img, sigma)
    half_tr = 0.5 * (ixx + iyy)
    rad = jnp.sqrt(jnp.maximum(
        0.25 * (ixx - iyy) ** 2 + ixy * ixy, 0.0))
    return half_tr - rad


# ---------------------------------------------------------------------------
# FAST segment test
# ---------------------------------------------------------------------------
# Bresenham circle of radius 3: 16 offsets in order.
FAST_OFFSETS = np.array([
    (-3, 0), (-3, 1), (-2, 2), (-1, 3), (0, 3), (1, 3), (2, 2), (3, 1),
    (3, 0), (3, -1), (2, -2), (1, -3), (0, -3), (-1, -3), (-2, -2), (-3, -1),
], np.int32)   # (dy, dx)


def _has_arc(flags, arc: int):
    """OR over the 16 start positions of "flags[s .. s+arc-1] (mod 16) all
    true".  ``runs[n][s]`` (all of flags[s .. s+n-1]) is built by
    doubling, then the arc is the AND of its binary-decomposed pieces:
    log2(arc) ANDs per start instead of arc - 1."""
    runs = {1: flags}
    n = 1
    while 2 * n <= arc:
        runs[2 * n] = [runs[n][s] & runs[n][(s + n) % 16] for s in range(16)]
        n *= 2
    arc_at, off = list(runs[n]), n
    for p in sorted(runs, reverse=True):
        if off + p <= arc:
            arc_at = [a & runs[p][(s + off) % 16] for s, a in enumerate(arc_at)]
            off += p
    hit = arc_at[0]
    for a in arc_at[1:]:
        hit = hit | a
    return hit


def fast_from_padded(x, h: int, w: int, threshold: float, arc: int):
    """FAST-N score of the [..., >= h+6, w+6] image ``x`` (3-px padded) ->
    [..., h, w].  Branch-free: the 16 circle neighbours are shifted slices,
    kept as a list — no [16, H, W] stack — so the working set stays a few
    image-sized maps (a [16, 64, 560, 560] stack ran a 64-tile batch out
    of a TPU v5e's 16 GB).  The Pallas kernel (`kernels/fastscore.py`)
    runs this same function on VMEM values."""
    center = x[..., 3:3 + h, 3:3 + w]
    circ = [x[..., 3 + dy:3 + dy + h, 3 + dx:3 + dx + w]
            for (dy, dx) in FAST_OFFSETS]
    brighter = [c > center + threshold for c in circ]
    darker = [c < center - threshold for c in circ]
    is_corner = _has_arc(brighter, arc) | _has_arc(darker, arc)
    diff = [jnp.abs(c - center) - threshold for c in circ]
    score_b = sum(jnp.where(b, d, 0.0) for b, d in zip(brighter, diff))
    score_d = sum(jnp.where(dk, d, 0.0) for dk, d in zip(darker, diff))
    return jnp.where(is_corner, jnp.maximum(score_b, score_d), 0.0)


def fast_score(img, threshold: float = 0.15, arc: int = 9,
               use_pallas: bool = False):
    """FAST-N score map: 0 where not a corner, else sum |I_p - I_center| - t
    over the contiguous arc pixels (OpenCV-style score)."""
    if use_pallas:
        from repro.kernels.ops import fast_score as _pallas
        return _pallas(img, threshold=threshold, arc=arc)
    p = jnp.pad(img, [(0, 0)] * (img.ndim - 2) + [(3, 3), (3, 3)],
                mode="reflect")
    return fast_from_padded(p, img.shape[-2], img.shape[-1], threshold, arc)


# ---------------------------------------------------------------------------
# SIFT detection: DoG scale-space extrema
# ---------------------------------------------------------------------------
def sift_dog_response(img, n_octaves=4, scales_per_octave=3,
                      contrast_threshold=0.04, use_pallas: bool = False):
    """Returns the octave-0 extrema response map [..., H, W] (full-res) plus
    per-octave responses; response = |DoG| where the pixel is a 3x3x3
    scale-space extremum above the contrast threshold, else 0.

    Consumes the fused extrema map from ``fused_octave_response`` directly:
    per octave, one fused computation (a single Pallas DMA on TPU) yields
    the response and the next octave's seed level — no Gaussian/DoG pyramid
    is materialized.  Matches the level-by-level path
    (``sift_dog_response_levelwise``, kept as the tests' oracle) to ~2 ulp with
    identical thresholded detection masks (Table-2 counts unchanged).
    """
    base = blur_separable(img, 1.6, use_pallas)
    responses = []
    for o in range(n_octaves):
        resp, seed = fused_octave_response(
            base, scales_per_octave, contrast_threshold,
            use_pallas=use_pallas)
        responses.append(resp)
        base = downsample2(seed)
    return responses


def sift_dog_response_levelwise(img, n_octaves=4, scales_per_octave=3,
                                contrast_threshold=0.04,
                                use_pallas: bool = False):
    """The seed's level-by-level SIFT path (gaussian_pyramid -> dog_pyramid
    -> 26-neighbour stack).  Kept only as the oracle the equivalence tests
    compare the fused path against; not used by the engine.  Uses the seed
    blur formulation, so the oracle is the seed's code, not just its math."""
    octs = gaussian_pyramid(img, n_octaves, scales_per_octave,
                            use_pallas=use_pallas,
                            blur_fn=blur_separable_seed)
    dogs = dog_pyramid(octs)
    responses = []
    for d in dogs:                                          # [..., S, H, W]
        s = d.shape[-3]
        mid = d[..., 1:s - 1, :, :]
        p = jnp.pad(d, [(0, 0)] * (d.ndim - 3) + [(0, 0), (1, 1), (1, 1)],
                    mode="reflect")
        neigh = []
        for ds in (-1, 0, 1):
            for dy in (-1, 0, 1):
                for dx in (-1, 0, 1):
                    if ds == 0 and dy == 0 and dx == 0:
                        continue
                    neigh.append(p[..., 1 + ds:1 + ds + s - 2,
                                   1 + dy:1 + dy + mid.shape[-2],
                                   1 + dx:1 + dx + mid.shape[-1]])
        neigh = jnp.stack(neigh, axis=0)
        is_max = (mid > neigh.max(axis=0))
        is_min = (mid < neigh.min(axis=0))
        resp = jnp.where((is_max | is_min)
                         & (jnp.abs(mid) > contrast_threshold),
                         jnp.abs(mid), 0.0)
        responses.append(resp.max(axis=-3))                 # over scales
    return responses


# ---------------------------------------------------------------------------
# SURF detection: fast-Hessian (box-filter approximation, 9x9 lobe)
# ---------------------------------------------------------------------------
def surf_hessian_response(img, use_pallas: bool = False):
    """det(H_approx) with 9x9 box filters (SURF's first scale), normalized.

    Dxx: lobes 5(h) x 3(w); weights (1, -2, 1); Dyy transposed; Dxy four
    3x3 corner boxes with weights (+1, -1, -1, +1).

    ``use_pallas`` is accepted for a uniform detector signature but the
    integral-image path is *pallas-exempt* (DESIGN.md §6): the summed-area
    table is two prefix scans + 8 gathers — already a single memory-bound
    sweep with no per-level rebuild to fuse, and the scans lower to plain
    adds that a hand-written kernel would not beat.
    """
    del use_pallas  # integral-image path is pallas-exempt (see docstring)
    ii = integral_image(img)
    # Dxx: three vertical-stacked boxes of 5x3 centered
    dxx = (box_sum(ii, -2, -4, 5, 3) - 2 * box_sum(ii, -2, -1, 5, 3)
           + box_sum(ii, -2, 2, 5, 3))
    dyy = (box_sum(ii, -4, -2, 3, 5) - 2 * box_sum(ii, -1, -2, 3, 5)
           + box_sum(ii, 2, -2, 3, 5))
    dxy = (box_sum(ii, -4, 1, 3, 3) + box_sum(ii, 1, -4, 3, 3)
           - box_sum(ii, -4, -4, 3, 3) - box_sum(ii, 1, 1, 3, 3))
    norm = 1.0 / 81.0
    dxx, dyy, dxy = dxx * norm, dyy * norm, dxy * norm
    return dxx * dyy - (0.9 * dxy) ** 2
