"""Patch extraction Pallas kernel: one window a keypoint, from a tile in
VMEM.

Every descriptor (SIFT, SURF, BRIEF, ORB) starts from a ``[K, p, p]``
stack of patches around its keypoints.  XLA's TPU gather serves either
form of that stack one index at a time: an element gather (``p**2``
flat indices a keypoint) or a window gather (a while loop of K
dynamic slices).  Here the padded tile is DMA'd to VMEM once (its
block index does not change along the keypoint axis, so Pallas does
not re-fetch it), and each keypoint takes one aligned block of
``rows x 256`` values, rotated into place along the sublanes and the
lanes.  Its first ``p x p`` values are the patch.  The start indices
arrive in SMEM, 128 keypoints a program.  Pure data movement: the
result equals slicing, bit for bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUBLANE = 8
WIN = 2 * LANE         # lanes a block spans: any 128-aligned start + < 129
KBLOCK = LANE          # keypoints a program (an SMEM block of 128 starts)


def _up(n: int, m: int) -> int:
    return -(-n // m) * m


def patches_pallas(img, y0, x0, *, size: int, interpret: bool):
    """img [H, W] f32; y0, x0 [K] int32 start indices, already clipped to
    ``[0, H - size]`` and ``[0, W - size]`` -> patches [K, size, size].
    A patch spans at most ``WIN - LANE + 1`` lanes."""
    h, w = img.shape
    if size > WIN - LANE + 1:
        raise ValueError(f"patch size {size} > {WIN - LANE + 1}")
    k = y0.shape[0]
    rows = _up(size + SUBLANE - 1, SUBLANE)    # aligned rows holding a patch
    hp = max(rows, _up(h, SUBLANE))
    wp = max(WIN, _up(w, LANE))
    if (hp, wp) != (h, w):
        img = jnp.pad(img, ((0, hp - h), (0, wp - w)))
    nk = -(-k // KBLOCK)
    if nk * KBLOCK != k:
        y0 = jnp.pad(y0, (0, nk * KBLOCK - k))
        x0 = jnp.pad(x0, (0, nk * KBLOCK - k))

    def kernel(y_ref, x_ref, img_ref, o_ref):
        def one(i, carry):
            y, x = y_ref[0, i], x_ref[0, i]
            r = pl.multiple_of(
                jnp.minimum(y // SUBLANE * SUBLANE, hp - rows), SUBLANE)
            c = pl.multiple_of(jnp.minimum(x // LANE * LANE, wp - WIN), LANE)
            blk = img_ref[pl.ds(r, rows), pl.ds(c, WIN)]
            blk = pltpu.roll(blk, (rows - (y - r)) % rows, 0)
            blk = pltpu.roll(blk, (WIN - (x - c)) % WIN, 1)
            o_ref[i] = blk[:size, :size]
            return carry

        jax.lax.fori_loop(0, KBLOCK, one, 0)

    starts = pl.BlockSpec((None, 1, KBLOCK), lambda j: (j, 0, 0),
                          memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        kernel,
        grid=(nk,),
        in_specs=[starts, starts,
                  pl.BlockSpec((hp, wp), lambda j: (0, 0))],
        out_specs=pl.BlockSpec((KBLOCK, size, size), lambda j: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nk * KBLOCK, size, size), img.dtype),
        interpret=interpret,
        name="patches",
    )(y0.reshape(nk, 1, KBLOCK), x0.reshape(nk, 1, KBLOCK), img)
    return out[:k]
