"""The benchmark's own input generators, made from ``--seed`` alone.

``synthetic_scene`` follows the program's Landsat-8-like generator
(``data/landsat.py``): smooth terrain, fields with crisp corners, point
targets and sensor noise, made on the device in one call.  ``tile_scene`` is a copy of the program's
tiler (``core/bundle.py``): halo-padded fixed tiles with the header row
``(scene_id, ty, tx, valid_h, valid_w, pad)``.  Copies, so that a later
change to the program cannot change what the benchmark feeds it."""
from __future__ import annotations

import functools

import jax
import numpy as np


def sub_seed(seed: int, *tags: int) -> int:
    """A 32-bit seed for numpy's legacy RNG from any whole ``seed`` (the
    benchmark's seeds go past 2**31) and integer tags."""
    return int(np.random.SeedSequence([int(seed) % 2 ** 63, *tags])
               .generate_state(1)[0])


def synthetic_scene(h: int, w: int, seed: int = 0,
                    density: float = 1.0) -> np.ndarray:
    """Grayscale float32 [h, w] in [0, 1], made on the default device in
    one jitted call and copied to the host."""
    n_fields = max(4, int(density * (h * w) / 20000))
    return np.asarray(_scene(h, w, n_fields, jax_key(seed)))


def jax_key(seed: int):
    return jax.random.key(seed % 2 ** 32)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _scene(h, w, n_fields, key):
    """The program's recipe: blocky terrain smoothed twice, rectangular
    fields of +-0.35 with crisp corners, 3x3 point targets of +0.5, and
    sensor noise.  Fields and targets are added through one summed-area
    table (+v / -v at each box's corners) instead of a loop."""
    import jax.numpy as jnp
    ks = jax.random.split(key, 10)
    ch, cw = max(h // 64, 2), max(w // 64, 2)
    coarse = jax.random.uniform(ks[0], (ch, cw), jnp.float32)
    t = jnp.repeat(jnp.repeat(coarse, h // ch + 1, 0), w // cw + 1, 1)
    t = t[:h, :w]
    for _ in range(2):
        t = 0.25 * (jnp.roll(t, 1, 0) + jnp.roll(t, -1, 0)
                    + jnp.roll(t, 1, 1) + jnp.roll(t, -1, 1))
    n_blobs = max(2, n_fields // 4)
    y0 = jax.random.randint(ks[1], (n_fields,), 0, max(h - 8, 1))
    x0 = jax.random.randint(ks[2], (n_fields,), 0, max(w - 8, 1))
    y1 = jnp.minimum(y0 + jax.random.randint(ks[3], (n_fields,), 6,
                                             max(h // 8, 7)), h)
    x1 = jnp.minimum(x0 + jax.random.randint(ks[4], (n_fields,), 6,
                                             max(w // 8, 7)), w)
    v = jax.random.uniform(ks[5], (n_fields,), jnp.float32, -0.35, 0.35)
    by = jax.random.randint(ks[6], (n_blobs,), 2, max(h - 3, 3))
    bx = jax.random.randint(ks[7], (n_blobs,), 2, max(w - 3, 3))
    y0, x0 = jnp.concatenate([y0, by - 1]), jnp.concatenate([x0, bx - 1])
    y1, x1 = jnp.concatenate([y1, by + 2]), jnp.concatenate([x1, bx + 2])
    v = jnp.concatenate([v, jnp.full((n_blobs,), 0.5, jnp.float32)])
    corners = jnp.zeros((h + 1, w + 1), jnp.float32)
    for ys, xs, sign in ((y0, x0, 1), (y0, x1, -1), (y1, x0, -1),
                         (y1, x1, 1)):
        corners = corners.at[ys, xs].add(sign * v)
    boxes = jnp.cumsum(jnp.cumsum(corners, 0), 1)[:h, :w]
    noise = 0.01 * jax.random.normal(ks[8], (h, w), jnp.float32)
    return jnp.clip(0.5 * t + boxes + noise, 0.0, 1.0)


def tile_scene(gray: np.ndarray, tile: int, halo: int, scene_id: int = 0):
    """Cut one scene into halo-padded tiles in raster order: ``(tiles
    float32 [n, tile+2*halo, tile+2*halo], headers int32 [n, 6])``."""
    h, w = gray.shape
    ny, nx = (h + tile - 1) // tile, (w + tile - 1) // tile
    padded = np.pad(gray, ((halo, halo + ny * tile - h),
                           (halo, halo + nx * tile - w)), mode="reflect")
    side = tile + 2 * halo
    tiles = np.empty((ny * nx, side, side), np.float32)
    headers = np.zeros((ny * nx, 6), np.int32)
    for ty in range(ny):
        for tx in range(nx):
            i = ty * nx + tx
            y0, x0 = ty * tile, tx * tile
            tiles[i] = padded[y0:y0 + side, x0:x0 + side]
            headers[i] = (scene_id, ty, tx, min(tile, h - y0),
                          min(tile, w - x0), 0)
    return tiles, headers


def bundles(tiles: np.ndarray, headers: np.ndarray, n: int):
    """Consecutive ``n``-tile bundles; the last is filled with pad-flagged
    empty tiles, as the streaming ingest cuts them."""
    out = []
    for i in range(0, len(tiles), n):
        t, h = tiles[i:i + n], headers[i:i + n]
        if len(t) < n:
            extra = n - len(t)
            t = np.concatenate([t, np.zeros((extra, *t.shape[1:]),
                                            np.float32)])
            pad = np.zeros((extra, 6), np.int32)
            pad[:, 5] = 1
            h = np.concatenate([h, pad])
        out.append((t, h))
    return out


def interior_px(headers: np.ndarray) -> int:
    """Scene pixels a bundle owns: the valid interiors of its real tiles."""
    real = headers[:, 5] == 0
    return int((headers[real, 3].astype(np.int64) * headers[real, 4]).sum())
