"""Feature descriptors: SIFT (128-d), SURF (64-d), BRIEF (256-bit),
ORB (steered BRIEF, 256-bit).

Descriptors are computed at capacity-K keypoints per tile with static
shapes: patch extraction takes one window a keypoint, clipped at tile
borders (a Pallas kernel on a TPU, `kernels/patches.py`); histogramming
is a fixed-order one-hot accumulation (see DESIGN.md §5 for why the
descriptor math is not a Pallas kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pyramid import blur_separable, sobel_gradients
from repro.kernels import ops


def extract_patches(img, ys, xs, size: int):
    """img [H,W]; ys,xs [K] (patch centers) -> patches [K, size, size].
    Start indices clip so patches near borders stay in-bounds.

    One window a keypoint, addressed by its two start indices, shared by
    the SIFT/SURF/BRIEF/ORB descriptor stages (``kernels/ops.py::patches``:
    a Pallas kernel on a TPU, XLA's window gather elsewhere).  Gathering
    ``size**2`` flat indices a keypoint gives the same values and ran 28x
    (18 px) to 122x (45 px) slower on a TPU v5e (docs/kernels.md).
    """
    h, w = img.shape
    half = size // 2
    y0 = jnp.clip(ys - half, 0, h - size)                   # [K]
    x0 = jnp.clip(xs - half, 0, w - size)
    return ops.patches(img, y0, x0, size=size)


# ---------------------------------------------------------------------------
# SIFT descriptor
# ---------------------------------------------------------------------------
def _gaussian_window(size, sigma):
    c = (size - 1) / 2.0
    y = np.arange(size) - c
    g = np.exp(-0.5 * (y / sigma) ** 2)
    return jnp.asarray(np.outer(g, g).astype(np.float32))


def _histogram(bins, weights, n_bins: int):
    """Weighted histograms of patches: bins, weights [K, p, p] ->
    [K, n_bins], each pixel added in raster order.  A scatter-add leaves
    the order of repeated bins to the backend, and a TPU picks it by the
    batch shape (a tile's descriptors rounded differently in a batch of
    16 and of 64); this loop fixes it on every backend."""
    k = bins.shape[0]
    bins = bins.reshape(k, -1)
    weights = weights.reshape(k, -1)
    ids = jnp.arange(n_bins, dtype=bins.dtype)

    def add(p, hist):
        b = jax.lax.dynamic_index_in_dim(bins, p, axis=1)          # [K, 1]
        w = jax.lax.dynamic_index_in_dim(weights, p, axis=1)
        return hist + jnp.where(b == ids, w, 0.0)

    return jax.lax.fori_loop(0, bins.shape[1], add,
                             jnp.zeros((k, n_bins), weights.dtype))


def sift_descriptors(img, ys, xs, n_bins=8, n_cells=4, patch=16):
    """128-d SIFT descriptors at keypoints.  [K] -> [K, 128] (L2-normalized,
    0.2-clipped).  Orientation from a 36-bin gradient histogram; spatial
    binning is hard-assignment (trilinear interpolation omitted — counts and
    invariances preserved; noted in DESIGN.md)."""
    g = patch + 2
    patches = extract_patches(img, ys, xs, g)               # [K,g,g]
    gx, gy = sobel_gradients(patches)
    gx = gx[:, 1:-1, 1:-1]
    gy = gy[:, 1:-1, 1:-1]                                  # [K,p,p]
    mag = jnp.sqrt(gx * gx + gy * gy + 1e-12)
    ang = jnp.arctan2(gy, gx)                               # [-pi, pi]

    # --- dominant orientation: 36-bin weighted histogram -------------------
    w36 = _gaussian_window(patch, patch / 3.0)
    bins36 = jnp.floor((ang + np.pi) / (2 * np.pi) * 36).astype(jnp.int32) % 36
    hist36 = _histogram(bins36, mag * w36, 36)
    theta = (jnp.argmax(hist36, axis=-1).astype(jnp.float32) + 0.5) \
        / 36.0 * 2 * np.pi - np.pi                          # [K]

    # --- rotate gradient field by -theta, bin into 4x4x8 -------------------
    rel_ang = (ang - theta[:, None, None] + 3 * np.pi) % (2 * np.pi)
    obins = jnp.floor(rel_ang / (2 * np.pi) * n_bins).astype(jnp.int32) % n_bins
    cell = patch // n_cells
    yy = jnp.arange(patch) // cell
    cell_idx = (yy[:, None] * n_cells + yy[None, :]).astype(jnp.int32)
    flat_bin = cell_idx[None] * n_bins + obins               # [K,p,p]
    wgt = mag * _gaussian_window(patch, patch / 2.0)
    desc = _histogram(flat_bin, wgt, n_cells * n_cells * n_bins)
    desc = desc / jnp.maximum(
        jnp.linalg.norm(desc, axis=-1, keepdims=True), 1e-6)
    desc = jnp.minimum(desc, 0.2)
    desc = desc / jnp.maximum(
        jnp.linalg.norm(desc, axis=-1, keepdims=True), 1e-6)
    return desc


# ---------------------------------------------------------------------------
# SURF descriptor
# ---------------------------------------------------------------------------
def surf_descriptors(img, ys, xs, patch=20):
    """64-d SURF: 4x4 subregions × (Σdx, Σ|dx|, Σdy, Σ|dy|) of Haar responses."""
    g = patch + 2
    patches = extract_patches(img, ys, xs, g)
    # Haar responses ~ central differences on the smoothed patch
    sm = blur_separable(patches, 1.0)
    dx = sm[:, 1:-1, 2:] - sm[:, 1:-1, :-2]
    dy = sm[:, 2:, 1:-1] - sm[:, :-2, 1:-1]                 # [K,p,p]
    w = _gaussian_window(patch, 3.3)
    dx, dy = dx * w, dy * w
    sub = patch // 4
    dxs = dx.reshape(-1, 4, sub, 4, sub)
    dys = dy.reshape(-1, 4, sub, 4, sub)
    feats = jnp.stack([
        dxs.sum(axis=(2, 4)), jnp.abs(dxs).sum(axis=(2, 4)),
        dys.sum(axis=(2, 4)), jnp.abs(dys).sum(axis=(2, 4)),
    ], axis=-1)                                             # [K,4,4,4]
    desc = feats.reshape(-1, 64)
    return desc / jnp.maximum(
        jnp.linalg.norm(desc, axis=-1, keepdims=True), 1e-6)


# ---------------------------------------------------------------------------
# BRIEF / ORB descriptors (binary)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=4)
def brief_pairs(n_bits: int = 256, patch: int = 31, seed: int = 7):
    """The fixed BRIEF sampling pattern: isotropic Gaussian, sigma=patch/5
    (Calonder et al. 2010, G I).  Returns int32 [n_bits, 4] = (y1,x1,y2,x2)."""
    rng = np.random.RandomState(seed)
    sigma = patch / 5.0
    pts = np.clip(rng.randn(n_bits, 4) * sigma, -(patch // 2), patch // 2)
    return np.round(pts).astype(np.int32)


def _sample_pairs(patches, pairs, patch):
    """patches [K,p,p]; pairs [n,4] (offsets from center) -> bits [K,n]."""
    half = patch // 2
    y1 = pairs[:, 0] + half
    x1 = pairs[:, 1] + half
    y2 = pairs[:, 2] + half
    x2 = pairs[:, 3] + half
    flat = patches.reshape(patches.shape[0], -1)
    i1 = y1 * patch + x1
    i2 = y2 * patch + x2
    v1 = jnp.take(flat, i1, axis=1)
    v2 = jnp.take(flat, i2, axis=1)
    return (v1 < v2)


def pack_bits(bits):
    """bool [K, n] -> uint32 [K, n//32]."""
    k, n = bits.shape
    b = bits.reshape(k, n // 32, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return (b << shifts).sum(axis=-1, dtype=jnp.uint32)


def brief_descriptors(img, ys, xs, n_bits=256, patch=31):
    """BRIEF: smoothed-intensity pair tests -> packed uint32 [K, n_bits/32]."""
    sm = blur_separable(img, 2.0)
    patches = extract_patches(sm, ys, xs, patch)
    pairs = jnp.asarray(brief_pairs(n_bits, patch))
    return pack_bits(_sample_pairs(patches, pairs, patch))


def orb_orientation(patches):
    """Intensity-centroid orientation (Rublee et al. 2011): theta [K]."""
    p = patches.shape[-1]
    c = (p - 1) / 2.0
    ys = jnp.arange(p) - c
    m10 = (patches * ys[None, None, :]).sum(axis=(-2, -1))   # x moment
    m01 = (patches * ys[None, :, None]).sum(axis=(-2, -1))   # y moment
    return jnp.arctan2(m01, m10)


def orb_descriptors(img, ys, xs, n_bits=256, patch=31):
    """ORB = oriented FAST + rotated BRIEF: the pair pattern is rotated by
    the patch orientation (discretized to 2π/30 as in the paper)."""
    sm = blur_separable(img, 2.0)
    big = patch + 14                                        # rotation margin
    patches = extract_patches(sm, ys, xs, big)
    theta = orb_orientation(
        patches[:, 7:7 + patch, 7:7 + patch])               # [K]
    step = 2 * np.pi / 30.0
    theta_q = jnp.round(theta / step) * step
    cos, sin = jnp.cos(theta_q), jnp.sin(theta_q)           # [K]
    pairs = jnp.asarray(brief_pairs(n_bits, patch)).astype(jnp.float32)
    # rotate both endpoints: (y,x) -> (x sin + y cos, x cos - y sin)
    def rot(y, x):
        ry = jnp.round(x[None, :] * sin[:, None] + y[None, :] * cos[:, None])
        rx = jnp.round(x[None, :] * cos[:, None] - y[None, :] * sin[:, None])
        return ry.astype(jnp.int32), rx.astype(jnp.int32)
    ry1, rx1 = rot(pairs[:, 0], pairs[:, 1])
    ry2, rx2 = rot(pairs[:, 2], pairs[:, 3])
    half = big // 2
    with jax.named_scope("pairs"):
        v1 = _select_points(patches, ry1 + half, rx1 + half)
        v2 = _select_points(patches, ry2 + half, rx2 + half)
    return pack_bits(v1 < v2)


def _select_points(patches, ys, xs):
    """patches [K,p,p]; ys, xs [K,n] in [0, p) -> patches[k, ys, xs], [K,n].

    Each keypoint reads its own rotated points, so the lookup is a one-hot
    contraction, not an element gather (one value at a time on a TPU v5e,
    ~14.5 ns a value: docs/kernels.md).  Columns first: the MXU contracts
    the patch with the column one-hot [K,p,n], the n points in the lanes;
    a masked sum over the p rows then picks the row.  Exact: at HIGHEST
    precision each f32 value times 1.0 comes back whole, and every other
    term is 0."""
    pos = jnp.arange(patches.shape[-1], dtype=ys.dtype)[None, :, None]
    cols = (xs[:, None, :] == pos).astype(patches.dtype)          # [K,p,n]
    picked = jnp.einsum("krc,kcb->krb", patches, cols,
                        precision=jax.lax.Precision.HIGHEST)      # [K,p,n]
    return jnp.where(ys[:, None, :] == pos, picked, 0.0).sum(axis=1)
