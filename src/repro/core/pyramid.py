"""Gaussian scale space and DoG pyramid (SIFT/SURF substrate).

Blur is separable; the hot loop optionally dispatches to the Pallas kernel
(`repro.kernels.blur`) on TPU, with the pure-jnp path as reference and CPU
fallback.

The SIFT hot path no longer materializes the pyramid level-by-level:
``fused_octave_response`` produces a whole octave's extrema response (and
the next octave's seed level) in one fused computation — on TPU a single
``pallas_call`` (`repro.kernels.scalespace`), on CPU a streaming jnp path
that never builds the 26-neighbour stack.  ``gaussian_pyramid`` /
``dog_pyramid`` remain as the level-by-level oracle of the equivalence
tests (see DESIGN.md §6).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.lru_cache(maxsize=64)
def gaussian_kernel_1d(sigma: float, radius: int = 0) -> np.ndarray:
    if radius == 0:
        radius = max(1, int(np.ceil(3.0 * sigma)))
    x = np.arange(-radius, radius + 1, dtype=np.float32)
    k = np.exp(-0.5 * (x / sigma) ** 2)
    return (k / k.sum()).astype(np.float32)


def blur_separable(img, sigma: float, use_pallas: bool = False):
    """img [..., H, W] -> gaussian blurred (reflect padding).

    One reflect pad + two valid passes (W then H) with no transposes —
    the same per-pixel add chain as the seed's pad-per-pass/transpose
    formulation (``blur_separable_seed``), ~10x faster on CPU XLA, which
    materialized every transpose and pad.  Values agree to <= ~2 ulp (XLA
    may contract mul+add to FMA differently across fusion boundaries);
    Table-2 detection masks are identical
    (``tests/test_kernels.py::test_fused_sift_response_matches_levelwise``).
    """
    if use_pallas:
        from repro.kernels.ops import gaussian_blur as _pallas_blur
        return _pallas_blur(img, sigma)
    taps = gaussian_kernel_1d(float(sigma))
    r = (len(taps) - 1) // 2
    h, w = img.shape[-2], img.shape[-1]
    xp = jnp.pad(img, [(0, 0)] * (img.ndim - 2) + [(r, r), (r, r)],
                 mode="reflect")
    tmp = sum(float(taps[j]) * xp[..., :, j:j + w] for j in range(2 * r + 1))
    return sum(float(taps[i]) * tmp[..., i:i + h, :] for i in range(2 * r + 1))


def blur_separable_seed(img, sigma: float, use_pallas: bool = False):
    """The seed's blur formulation: pad per pass, convolve along the last
    dim, transpose between passes.  Numerically identical to
    ``blur_separable``; kept only as the equivalence tests' oracle."""
    if use_pallas:
        from repro.kernels.ops import gaussian_blur as _pallas_blur
        return _pallas_blur(img, sigma)
    k = jnp.asarray(gaussian_kernel_1d(float(sigma)))
    r = (k.shape[0] - 1) // 2

    def conv_last(x):
        xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(r, r)], mode="reflect")
        windows = [xp[..., i:i + x.shape[-1]] for i in range(2 * r + 1)]
        return sum(w * k[i] for i, w in enumerate(windows))

    out = conv_last(img)                     # along W
    out = jnp.swapaxes(conv_last(jnp.swapaxes(out, -1, -2)), -1, -2)  # along H
    return out


def downsample2(img):
    return img[..., ::2, ::2]


@functools.lru_cache(maxsize=32)
def octave_increments(scales_per_octave: int, sigma0: float = 1.6):
    """Incremental blur sigmas for one octave's levels 1..n_scales-1.

    Level s has total sigma ``sigma0 * 2**(s/scales_per_octave)``; each level
    is produced from the previous by a blur of the returned increment (the
    Gaussian semigroup property), so taps can be compile-time constants.
    """
    n_scales = scales_per_octave + 3
    k = 2.0 ** (1.0 / scales_per_octave)
    incs = []
    sigma_prev = sigma0
    for s in range(1, n_scales):
        sigma_total = sigma0 * (k ** s)
        incs.append(float(np.sqrt(max(sigma_total ** 2 - sigma_prev ** 2,
                                      1e-6))))
        sigma_prev = sigma_total
    return tuple(incs)


def _ring8_and_full9(dog_level):
    """3x3 neighbourhood maxima of one DoG level [..., H, W].

    Returns (full9_max, full9_min, ring8_max, ring8_min): the max/min over
    the full 3x3 window and over the 8-neighbour ring (centre excluded),
    computed with separable shifted-max chains instead of a 26-image stack —
    exact (fp max is associative) but ~4x fewer buffers than stacking.
    """
    h, w = dog_level.shape[-2:]
    p = jnp.pad(dog_level, [(0, 0)] * (dog_level.ndim - 2) + [(1, 1), (1, 1)],
                mode="reflect")
    rows = lambda y: p[..., y:y + h + 2, :]                  # noqa: E731
    cols = lambda x, a: a[..., :, x:x + w]                   # noqa: E731
    # horizontal 3-max / left-right 2-max on the (h+2)-row band
    band = p
    h3mx = jnp.maximum(jnp.maximum(cols(0, band), cols(1, band)),
                       cols(2, band))                        # [..., h+2, w]
    h3mn = jnp.minimum(jnp.minimum(cols(0, band), cols(1, band)),
                       cols(2, band))
    lrmx = jnp.maximum(cols(0, band), cols(2, band))
    lrmn = jnp.minimum(cols(0, band), cols(2, band))
    row = lambda y, a: a[..., y:y + h, :]                    # noqa: E731
    full9_max = jnp.maximum(jnp.maximum(row(0, h3mx), row(1, h3mx)),
                            row(2, h3mx))
    full9_min = jnp.minimum(jnp.minimum(row(0, h3mn), row(1, h3mn)),
                            row(2, h3mn))
    ring8_max = jnp.maximum(jnp.maximum(row(0, h3mx), row(2, h3mx)),
                            row(1, lrmx))
    ring8_min = jnp.minimum(jnp.minimum(row(0, h3mn), row(2, h3mn)),
                            row(1, lrmn))
    return full9_max, full9_min, ring8_max, ring8_min


def fused_extrema_response(dogs, contrast_threshold):
    """Fused 3x3x3 DoG-extrema response: max over mid scales of |DoG| where
    the pixel is a strict scale-space extremum above the contrast threshold.

    ``dogs`` is a list of per-scale DoG images [..., H, W] (len >= 3).
    Bitwise-identical to the 26-neighbour-stack formulation (max/min
    decomposition is exact) but streams scale slabs instead of materializing
    a [26, S-2, H, W] volume.
    """
    stats = [_ring8_and_full9(d) for d in dogs]
    resp = None
    for s in range(1, len(dogs) - 1):
        below_mx, below_mn, _, _ = stats[s - 1]
        above_mx, above_mn, _, _ = stats[s + 1]
        _, _, ring_mx, ring_mn = stats[s]
        mid = dogs[s]
        neigh_max = jnp.maximum(jnp.maximum(below_mx, above_mx), ring_mx)
        neigh_min = jnp.minimum(jnp.minimum(below_mn, above_mn), ring_mn)
        is_ext = (mid > neigh_max) | (mid < neigh_min)
        r = jnp.where(is_ext & (jnp.abs(mid) > contrast_threshold),
                      jnp.abs(mid), 0.0)
        resp = r if resp is None else jnp.maximum(resp, r)
    return resp


def fused_octave_response(base, scales_per_octave: int,
                          contrast_threshold: float, sigma0: float = 1.6,
                          use_pallas: bool = False):
    """One octave of the SIFT detector, fused: (response, next-octave seed).

    ``base`` [..., H, W] is the octave's level 0 (already blurred to
    ``sigma0``).  Returns ``resp`` [..., H, W] — the 3x3x3 DoG-extrema
    response maxed over the octave's mid scales — and ``seed`` [..., H, W],
    the level with total sigma ``2*sigma0`` (downsample it to start the next
    octave).  No per-level pyramid list is materialized by the caller.

    Dispatch: ``use_pallas=True`` routes to the one-DMA Pallas kernel
    (`repro.kernels.scalespace`) when the octave's VMEM working set fits the
    budget (DESIGN.md §6); otherwise this streaming jnp path runs (it is
    also the CPU reference).
    """
    if use_pallas:
        from repro.kernels import ops as _ops
        h, w = base.shape[-2], base.shape[-1]
        if _ops.scalespace_fits_vmem(h, w, scales_per_octave, sigma0):
            return _ops.scalespace_octave(
                base, scales_per_octave=scales_per_octave,
                contrast_threshold=float(contrast_threshold), sigma0=sigma0)
    incs = octave_increments(scales_per_octave, sigma0)
    prev = base
    seed = None
    dogs = []
    for s, sigma_inc in enumerate(incs, start=1):
        cur = blur_separable(prev, sigma_inc)
        dogs.append(cur - prev)
        if s == scales_per_octave:
            seed = cur
        prev = cur
    resp = fused_extrema_response(dogs, contrast_threshold)
    return resp, seed


def gaussian_pyramid(img, n_octaves: int, scales_per_octave: int,
                     sigma0: float = 1.6, use_pallas: bool = False,
                     blur_fn=None):
    """Returns list of octaves; octave = [n_scales+3, ..., H_o, W_o].

    Level-by-level reference path: every level round-trips through HBM.
    The SIFT hot path uses ``fused_octave_response`` instead.  ``blur_fn``
    lets the seed oracle (``detectors.sift_dog_response_levelwise``) pin
    the seed blur formulation (``blur_separable_seed``); default is
    ``blur_separable``.
    """
    blur_fn = blur_separable if blur_fn is None else blur_fn
    octaves = []
    base = blur_fn(img, sigma0, use_pallas)
    for o in range(n_octaves):
        levels = [base]
        for sigma_inc in octave_increments(scales_per_octave, sigma0):
            levels.append(blur_fn(levels[-1], sigma_inc, use_pallas))
        octave = jnp.stack(levels, axis=-3)     # [..., n_scales, H, W]
        octaves.append(octave)
        # next octave seeds from the level with sigma = 2*sigma0
        base = downsample2(levels[scales_per_octave])
    return octaves


def dog_pyramid(octaves):
    """Difference-of-Gaussians per octave: [..., n_scales-1, H, W]."""
    return [o[..., 1:, :, :] - o[..., :-1, :, :] for o in octaves]


def sobel_gradients(img):
    """img [..., H, W] -> (gx, gy), Sobel, reflect padding."""
    p = jnp.pad(img, [(0, 0)] * (img.ndim - 2) + [(1, 1), (1, 1)],
                mode="reflect")
    # p[..., y, x]; slices for the 3x3 neighbourhood
    def sl(dy, dx):
        h, w = img.shape[-2], img.shape[-1]
        return p[..., 1 + dy:1 + dy + h, 1 + dx:1 + dx + w]
    gx = (sl(-1, 1) + 2 * sl(0, 1) + sl(1, 1)
          - sl(-1, -1) - 2 * sl(0, -1) - sl(1, -1)) / 8.0
    gy = (sl(1, -1) + 2 * sl(1, 0) + sl(1, 1)
          - sl(-1, -1) - 2 * sl(-1, 0) - sl(-1, 1)) / 8.0
    return gx, gy


def integral_image(img):
    """Summed-area table with a leading zero row/col: [..., H+1, W+1].

    The prefix sums are an explicit ``associative_scan``, not
    ``jnp.cumsum``: a TPU lowers cumsum to a windowed reduction whose
    accumulation blocks it picks by the batch shape, so the same tile
    summed in a batch of 16 and of 64 rounded differently.  Explicit adds
    fix the order on every backend and batch."""
    scan = functools.partial(jax.lax.associative_scan, jnp.add)
    ii = scan(scan(img, axis=img.ndim - 2), axis=img.ndim - 1)
    return jnp.pad(ii, [(0, 0)] * (img.ndim - 2) + [(1, 0), (1, 0)])


def box_sum(ii, y0, x0, h, w):
    """Box sums from an integral image, static offsets (for SURF filters).

    ii: [..., H+1, W+1]; returns [..., H, W] where out[y,x] = sum of the
    (h, w) box whose top-left is at (y + y0, x + x0) — out-of-range reads
    clamp to the image border (same convention as OpenCV's filter margin).
    """
    H = ii.shape[-2] - 1
    W = ii.shape[-1] - 1

    def at(dy, dx):
        ys = jnp.clip(jnp.arange(H) + dy, 0, H)
        xs = jnp.clip(jnp.arange(W) + dx, 0, W)
        return ii[..., ys[:, None], xs[None, :]]

    return (at(y0 + h, x0 + w) - at(y0, x0 + w)
            - at(y0 + h, x0) + at(y0, x0))
