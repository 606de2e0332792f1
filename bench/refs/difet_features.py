"""Plain reference of DIFET's per-tile features: the seven algorithms of
the paper (Harris, Shi-Tomasi, SIFT, SURF, FAST, BRIEF, ORB), 3x3 NMS,
interior ownership, capacity-K top-K and the descriptors, one tile at a
time in straightforward ``jax.numpy``, plus the two reduces the
deployments apply (the job's scene top-4K and the service's per-request
top-K) in numpy.

It imports nothing of the program.  It follows the semantics the program
states: OpenCV-style thresholds rescaled to [0, 1] images, octave-0 SIFT
extrema, SURF's 9x9 box-filter Hessian over an integral image summed in
a fixed order, FAST-9 with the OpenCV score, BRIEF's Gaussian pair
pattern from seed 7, ORB's pattern steered in steps of 2pi/30, and SIFT's
histograms added in raster order.  ``dtype`` is the precision it computes
in: float32, as the configurations state, or bfloat16 for the control."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ALGORITHMS = ("harris", "shi_tomasi", "sift", "surf", "fast", "brief", "orb")
DESCRIBED = {"sift": 128, "surf": 64, "brief": 8, "orb": 8}
RESPONSE_OF = {"harris": "harris", "shi_tomasi": "shi_tomasi",
               "sift": "sift", "surf": "surf", "fast": "fast",
               "brief": "fast", "orb": "fast"}


# ---- filters -----------------------------------------------------------------

def gauss_taps(sigma: float) -> np.ndarray:
    r = max(1, int(np.ceil(3.0 * sigma)))
    x = np.arange(-r, r + 1, dtype=np.float32)
    k = np.exp(-0.5 * (x / sigma) ** 2)
    return (k / k.sum()).astype(np.float32)


def blur(img, sigma):
    """Separable Gaussian with reflect padding: along W, then along H."""
    taps = gauss_taps(float(sigma))
    r = len(taps) // 2
    h, w = img.shape[-2:]
    xp = jnp.pad(img, [(0, 0)] * (img.ndim - 2) + [(r, r), (r, r)],
                 mode="reflect")
    tmp = sum(float(taps[j]) * xp[..., :, j:j + w] for j in range(2 * r + 1))
    return sum(float(taps[i]) * tmp[..., i:i + h, :]
               for i in range(2 * r + 1))


def sobel(img):
    h, w = img.shape[-2:]
    p = jnp.pad(img, [(0, 0)] * (img.ndim - 2) + [(1, 1), (1, 1)],
                mode="reflect")

    def at(dy, dx):
        return p[..., 1 + dy:1 + dy + h, 1 + dx:1 + dx + w]
    gx = (at(-1, 1) + 2 * at(0, 1) + at(1, 1)
          - at(-1, -1) - 2 * at(0, -1) - at(1, -1)) / 8.0
    gy = (at(1, -1) + 2 * at(1, 0) + at(1, 1)
          - at(-1, -1) - 2 * at(-1, 0) - at(-1, 1)) / 8.0
    return gx, gy


# ---- responses ---------------------------------------------------------------

def structure_tensor(img):
    gx, gy = sobel(img)
    return blur(gx * gx, 1.0), blur(gy * gy, 1.0), blur(gx * gy, 1.0)


def harris(img, p):
    ixx, iyy, ixy = structure_tensor(img)
    tr = ixx + iyy
    return (ixx * iyy - ixy * ixy) - p["harris_k"] * tr * tr


def shi_tomasi(img, p):
    ixx, iyy, ixy = structure_tensor(img)
    rad = jnp.sqrt(jnp.maximum(0.25 * (ixx - iyy) ** 2 + ixy * ixy, 0.0))
    return 0.5 * (ixx + iyy) - rad


CIRCLE = ((-3, 0), (-3, 1), (-2, 2), (-1, 3), (0, 3), (1, 3), (2, 2), (3, 1),
          (3, 0), (3, -1), (2, -2), (1, -3), (0, -3), (-1, -3), (-2, -2),
          (-3, -1))


def fast(img, p):
    """FAST-N: a corner where N contiguous circle pixels (cyclically) are
    all brighter or all darker than the centre by the threshold; its
    score is the larger of the summed excesses of the brighter and of
    the darker circle pixels."""
    t, n = p["fast_threshold"], p["fast_arc"]
    h, w = img.shape
    x = jnp.pad(img, 3, mode="reflect")
    c = x[3:3 + h, 3:3 + w]
    circ = [x[3 + dy:3 + dy + h, 3 + dx:3 + dx + w] for dy, dx in CIRCLE]
    corner = jnp.zeros((h, w), bool)
    for flags in ([v > c + t for v in circ], [v < c - t for v in circ]):
        for s in range(16):
            run = flags[s]
            for j in range(1, n):
                run = run & flags[(s + j) % 16]
            corner = corner | run
    diff = [jnp.abs(v - c) - t for v in circ]
    sb = sum(jnp.where(v > c + t, d, 0.0) for v, d in zip(circ, diff))
    sd = sum(jnp.where(v < c - t, d, 0.0) for v, d in zip(circ, diff))
    return jnp.where(corner, jnp.maximum(sb, sd), 0.0)


def sift(img, p):
    """|DoG| at strict 3x3x3 extrema of octave 0 above the contrast
    threshold, maxed over the octave's middle scales."""
    s = p["scales_per_octave"]
    thr = p["sift_contrast_threshold"] / s
    k = 2.0 ** (1.0 / s)
    levels = [blur(img, 1.6)]
    prev_sigma = 1.6
    for i in range(1, s + 3):
        total = 1.6 * k ** i
        levels.append(blur(levels[-1],
                           float(np.sqrt(max(total ** 2 - prev_sigma ** 2,
                                             1e-6)))))
        prev_sigma = total
    dogs = [b - a for a, b in zip(levels[:-1], levels[1:])]
    h, w = img.shape
    pads = [jnp.pad(d, 1, mode="reflect") for d in dogs]
    resp = None
    for i in range(1, len(dogs) - 1):
        mid = dogs[i]
        neigh = [pads[i + ds][1 + dy:1 + dy + h, 1 + dx:1 + dx + w]
                 for ds in (-1, 0, 1) for dy in (-1, 0, 1)
                 for dx in (-1, 0, 1) if (ds, dy, dx) != (0, 0, 0)]
        nmax = functools.reduce(jnp.maximum, neigh)
        nmin = functools.reduce(jnp.minimum, neigh)
        r = jnp.where(((mid > nmax) | (mid < nmin))
                      & (jnp.abs(mid) > thr), jnp.abs(mid), 0.0)
        resp = r if resp is None else jnp.maximum(resp, r)
    return resp


def integral(img):
    """Summed-area table with a leading zero row and column; the prefix
    sums add in the fixed order of an associative scan."""
    scan = functools.partial(jax.lax.associative_scan, jnp.add)
    ii = scan(scan(img, axis=0), axis=1)
    return jnp.pad(ii, ((1, 0), (1, 0)))


def box(ii, y0, x0, bh, bw):
    """Sum of the (bh, bw) box whose top-left is (y + y0, x + x0), reads
    clamped to the table."""
    H, W = ii.shape[0] - 1, ii.shape[1] - 1

    def at(dy, dx):
        ys = jnp.clip(jnp.arange(H) + dy, 0, H)
        xs = jnp.clip(jnp.arange(W) + dx, 0, W)
        return ii[ys[:, None], xs[None, :]]
    return at(y0 + bh, x0 + bw) - at(y0, x0 + bw) - at(y0 + bh, x0) \
        + at(y0, x0)


def surf(img, p):
    ii = integral(img)
    dxx = box(ii, -2, -4, 5, 3) - 2 * box(ii, -2, -1, 5, 3) \
        + box(ii, -2, 2, 5, 3)
    dyy = box(ii, -4, -2, 3, 5) - 2 * box(ii, -1, -2, 3, 5) \
        + box(ii, 2, -2, 3, 5)
    dxy = box(ii, -4, 1, 3, 3) + box(ii, 1, -4, 3, 3) \
        - box(ii, -4, -4, 3, 3) - box(ii, 1, 1, 3, 3)
    n = 1.0 / 81.0
    dxx, dyy, dxy = dxx * n, dyy * n, dxy * n
    return dxx * dyy - (0.9 * dxy) ** 2


RESPONSES = {"harris": harris, "shi_tomasi": shi_tomasi, "fast": fast,
             "sift": sift, "surf": surf}


def threshold(alg, p):
    return {"harris": p["harris_threshold"] * 1e-4,
            "shi_tomasi": p["shi_tomasi_threshold"] * 1e-2,
            "sift": p["sift_contrast_threshold"] / p["scales_per_octave"],
            "surf": p["surf_hessian_threshold"] / 255.0 ** 2,
            }.get(alg, 0.0)


# ---- selection ---------------------------------------------------------------

def nms3x3(resp):
    """Strict 3x3 maxima; on a plateau only the pixel of smallest
    row-major index survives."""
    h, w = resp.shape
    neg = jnp.pad(resp, 1, constant_values=-jnp.inf)
    win = [neg[1 + dy:1 + dy + h, 1 + dx:1 + dx + w]
           for dy in (-1, 0, 1) for dx in (-1, 0, 1)]
    mx = functools.reduce(jnp.maximum, win)
    idx = jnp.arange(h * w, dtype=jnp.int32).reshape(h, w)
    big = jnp.iinfo(jnp.int32).max
    cand = jnp.pad(jnp.where(resp >= mx, idx, big), 1, constant_values=big)
    first = functools.reduce(jnp.minimum,
                             [cand[1 + dy:1 + dy + h, 1 + dx:1 + dx + w]
                              for dy in (-1, 0, 1) for dx in (-1, 0, 1)])
    return jnp.where((resp >= mx) & (idx == first), resp, 0.0)


def patches(img, ys, xs, size):
    """[K, size, size] windows centred on the keypoints, start clipped so
    that each lies inside the image."""
    h, w = img.shape
    y0 = jnp.clip(ys - size // 2, 0, h - size)
    x0 = jnp.clip(xs - size // 2, 0, w - size)
    d = jnp.arange(size)
    return img[(y0[:, None] + d)[:, :, None], (x0[:, None] + d)[:, None, :]]


def gauss_window(size, sigma, dtype):
    y = np.arange(size) - (size - 1) / 2.0
    g = np.exp(-0.5 * (y / sigma) ** 2)
    return jnp.asarray(np.outer(g, g).astype(np.float32), dtype)


def histogram(bins, weights, n_bins):
    """Weighted histograms, each pixel added in raster order."""
    k = bins.shape[0]
    bins, weights = bins.reshape(k, -1), weights.reshape(k, -1)
    ids = jnp.arange(n_bins, dtype=bins.dtype)

    def add(i, hist):
        b = jax.lax.dynamic_slice_in_dim(bins, i, 1, axis=1)
        w = jax.lax.dynamic_slice_in_dim(weights, i, 1, axis=1)
        return hist + jnp.where(b == ids, w, 0.0)
    return jax.lax.fori_loop(0, bins.shape[1], add,
                             jnp.zeros((k, n_bins), weights.dtype))


def unit(v):
    return v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True), 1e-6)


def sift_desc(img, ys, xs):
    """4x4 cells x 8 orientations, rotated to the peak of a 36-bin
    gradient histogram, hard-assigned, normalised, clipped at 0.2 and
    normalised again."""
    pt = 16
    gx, gy = sobel(patches(img, ys, xs, pt + 2))
    gx, gy = gx[:, 1:-1, 1:-1], gy[:, 1:-1, 1:-1]
    mag = jnp.sqrt(gx * gx + gy * gy + 1e-12)
    ang = jnp.arctan2(gy, gx)
    b36 = jnp.floor((ang + np.pi) / (2 * np.pi) * 36).astype(jnp.int32) % 36
    h36 = histogram(b36, mag * gauss_window(pt, pt / 3.0, img.dtype), 36)
    theta = (jnp.argmax(h36, axis=-1).astype(img.dtype) + 0.5) / 36.0 \
        * 2 * np.pi - np.pi
    rel = (ang - theta[:, None, None] + 3 * np.pi) % (2 * np.pi)
    ob = jnp.floor(rel / (2 * np.pi) * 8).astype(jnp.int32) % 8
    cell = (jnp.arange(pt) // 4)
    flat = (cell[:, None] * 4 + cell[None, :]).astype(jnp.int32)[None] * 8 + ob
    d = histogram(flat, mag * gauss_window(pt, pt / 2.0, img.dtype), 128)
    return unit(jnp.minimum(unit(d), 0.2))


def surf_desc(img, ys, xs):
    """4x4 subregions x (sum dx, sum |dx|, sum dy, sum |dy|) of central
    differences of the 1.0-blurred patch, Gaussian-weighted."""
    pt = 20
    sm = blur(patches(img, ys, xs, pt + 2), 1.0)
    g = gauss_window(pt, 3.3, img.dtype)
    dx = (sm[:, 1:-1, 2:] - sm[:, 1:-1, :-2]) * g
    dy = (sm[:, 2:, 1:-1] - sm[:, :-2, 1:-1]) * g
    dxs, dys = dx.reshape(-1, 4, 5, 4, 5), dy.reshape(-1, 4, 5, 4, 5)
    f = jnp.stack([dxs.sum((2, 4)), jnp.abs(dxs).sum((2, 4)),
                   dys.sum((2, 4)), jnp.abs(dys).sum((2, 4))], -1)
    return unit(f.reshape(-1, 64))


def brief_pairs(n_bits=256, patch=31, seed=7):
    rng = np.random.RandomState(seed)
    pts = np.clip(rng.randn(n_bits, 4) * (patch / 5.0),
                  -(patch // 2), patch // 2)
    return np.round(pts).astype(np.int32)


def pack(bits):
    k, n = bits.shape
    b = bits.reshape(k, n // 32, 32).astype(jnp.uint32)
    return (b << jnp.arange(32, dtype=jnp.uint32)).sum(-1, dtype=jnp.uint32)


def brief_desc(img, ys, xs):
    pt = 31
    pp = patches(blur(img, 2.0), ys, xs, pt).reshape(len(ys), -1)
    q = brief_pairs() + pt // 2
    return pack(pp[:, q[:, 0] * pt + q[:, 1]] < pp[:, q[:, 2] * pt + q[:, 3]])


def orb_desc(img, ys, xs):
    pt, big = 31, 45
    pp = patches(blur(img, 2.0), ys, xs, big)
    core = pp[:, 7:7 + pt, 7:7 + pt]
    c = jnp.arange(pt, dtype=img.dtype) - (pt - 1) / 2.0
    theta = jnp.arctan2((core * c[None, :, None]).sum((1, 2)),
                        (core * c[None, None, :]).sum((1, 2)))
    step = 2 * np.pi / 30.0
    tq = jnp.round(theta / step) * step
    cos, sin = jnp.cos(tq)[:, None], jnp.sin(tq)[:, None]
    q = jnp.asarray(brief_pairs(), img.dtype)

    def rot(y, x):
        return (jnp.round(x * sin + y * cos).astype(jnp.int32) + big // 2,
                jnp.round(x * cos - y * sin).astype(jnp.int32) + big // 2)
    y1, x1 = rot(q[:, 0], q[:, 1])
    y2, x2 = rot(q[:, 2], q[:, 3])
    flat = pp.reshape(len(ys), -1)
    v1 = jnp.take_along_axis(flat, y1 * big + x1, axis=1)
    v2 = jnp.take_along_axis(flat, y2 * big + x2, axis=1)
    return pack(v1 < v2)


DESCRIBE = {"sift": sift_desc, "surf": surf_desc, "brief": brief_desc,
            "orb": orb_desc}


def tile_features(tile, header, p, algorithms, dtype=jnp.float32):
    """One tile: ``{alg: {count, ys, xs, scores, valid[, desc]}}`` with
    scene coordinates, K = ``max_keypoints_per_tile`` slots."""
    img = tile.astype(dtype)
    halo, k = p["halo"], p["max_keypoints_per_tile"]
    h, w = img.shape
    ys_ = jnp.arange(h)[:, None]
    xs_ = jnp.arange(w)[None, :]
    mask = ((ys_ >= halo) & (ys_ < halo + header[3]) & (xs_ >= halo)
            & (xs_ < halo + header[4]) & (header[5] == 0))
    resp, out = {}, {}
    for alg in algorithms:
        src = RESPONSE_OF[alg]
        if src not in resp:
            resp[src] = RESPONSES[src](img, p)
        r = resp[src]
        thr = threshold(alg, p)
        peaks = nms3x3(r)
        flat = jnp.where(mask & (peaks > thr), peaks, -jnp.inf).reshape(-1)
        scores, idx = jax.lax.top_k(flat, k)
        valid = jnp.isfinite(scores)
        yy = jnp.where(valid, idx // w, 0).astype(jnp.int32)
        xx = jnp.where(valid, idx % w, 0).astype(jnp.int32)
        f = {"count": (mask & (r > thr)).sum(dtype=jnp.int32),
             "scores": jnp.where(valid, scores, 0).astype(jnp.float32),
             "valid": valid,
             "ys": header[1] * p["tile"] + yy - halo,
             "xs": header[2] * p["tile"] + xx - halo}
        if alg in DESCRIBE:
            d = DESCRIBE[alg](img, yy, xx)
            f["desc"] = jnp.where(valid[:, None], d, 0).astype(
                jnp.uint32 if d.dtype == jnp.uint32 else jnp.float32)
        out[alg] = f
    return out


@functools.lru_cache(maxsize=16)
def _batched(p_items, algorithms, dtype_name):
    p = dict(p_items)
    return jax.jit(jax.vmap(functools.partial(
        tile_features, p=p, algorithms=algorithms,
        dtype=jnp.dtype(dtype_name))))


def features(tiles, headers, p, algorithms, dtype="float32", block=16,
             device=None):
    """Per-tile features of a batch, ``block`` tiles per call, as numpy
    ``{alg: {key: [n, ...]}}``."""
    keys = ("harris_k", "harris_threshold", "shi_tomasi_threshold",
            "fast_threshold", "fast_arc", "sift_contrast_threshold",
            "scales_per_octave", "surf_hessian_threshold", "halo", "tile",
            "max_keypoints_per_tile")
    fn = _batched(tuple((k, p[k]) for k in keys), tuple(algorithms), dtype)
    n = len(tiles)
    parts = []
    for i in range(0, n, block):
        t, h = tiles[i:i + block], headers[i:i + block]
        if len(t) < block:            # one shape per run: pad the last block
            t = np.concatenate([t, np.zeros((block - len(t), *t.shape[1:]),
                                            t.dtype)])
            h = np.concatenate([h, np.tile([[0, 0, 0, 0, 0, 1]],
                                           (block - len(h), 1))])
        if device is not None:
            t, h = jax.device_put(t, device), jax.device_put(h, device)
        parts.append(jax.device_get(fn(t, np.asarray(h, np.int32))))
    return {alg: {k: np.concatenate([q[alg][k] for q in parts])[:n]
                  for k in parts[0][alg]} for alg in algorithms}


def keypoint_set(scores, ys, xs, valid, desc=None):
    """The valid keypoints as ``{(y, x): (score, desc)}``."""
    out = {}
    for i in np.flatnonzero(valid):
        out[(int(ys[i]), int(xs[i]))] = (float(scores[i]),
                                         None if desc is None else desc[i])
    return out


def job_result(per_tile: dict, k: int) -> dict:
    """The job's reduce of one bundle: total count, and the scene top-4K
    over every tile's candidates (ties to the lower candidate index)."""
    s = np.where(per_tile["valid"], per_tile["scores"], -np.inf).reshape(-1)
    kk = min(4 * k, s.size)
    order = np.argsort(-s, kind="stable")[:kk]
    valid = np.isfinite(s[order])
    take = lambda a: a.reshape(s.size, *a.shape[2:])[order]  # noqa: E731
    out = {"total_count": int(per_tile["count"].sum()),
           "per_tile_count": per_tile["count"],
           "top_scores": np.where(valid, s[order], 0.0), "top_valid": valid,
           "top_ys": take(per_tile["ys"]), "top_xs": take(per_tile["xs"])}
    if "desc" in per_tile:
        out["top_desc"] = take(per_tile["desc"])
    return out


def request_result(per_tile: dict, i: int) -> dict:
    """The service's reduce of one request tile: its own count and its
    own valid keypoints."""
    out = {"total_count": int(per_tile["count"][i]),
           "top_scores": per_tile["scores"][i],
           "top_valid": per_tile["valid"][i],
           "top_ys": per_tile["ys"][i], "top_xs": per_tile["xs"][i]}
    if "desc" in per_tile:
        out["top_desc"] = per_tile["desc"][i]
    return out
