"""Row-strip grid shared by the per-pixel stencil kernels (harris, blur,
fast).

One program per whole tile holds every intermediate of a 560-px tile in
VMEM at once and unrolls the stencil over the whole tile: at the paper's
tile (512 + 2·24 halo) Harris and blur need more scoped VMEM than the
TPU compiler grants by default (16 MiB), and the FAST segment test
unrolls into a program the compiler does not finish in minutes.  Here
the grid is ``(tile, strip)``: the padded tile is still DMA'd to VMEM
once (its block index does not change along the strip axis, so Pallas
does not re-fetch it), and each program computes ``STRIP`` output rows
from its ``STRIP + 2·halo`` input rows.  The per-pixel arithmetic is the
same as one whole-tile program's, so results are unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

STRIP = 32        # output rows per program (a multiple of the 8 sublanes)


def _round8(n: int) -> int:
    return -(-n // 8) * 8


def strip_pallas(body, x_padded, *, h: int, w: int, halo: int,
                 interpret: bool, name: str, strip: int = STRIP):
    """Run ``body(x, rows) -> [rows, w]`` over row strips of each padded
    tile.

    ``x_padded`` is ``[n, h + 2*halo, w + 2*halo(+lane pad)]``; ``body``
    gets a VMEM value of at least ``rows + 2*halo`` rows (its first rows
    are the strip's window) and returns the ``[rows, w]`` output block.
    Rows are padded up to whole strips (the extra output rows are
    cropped), so any ``h`` works.  Returns ``[n, h, w]``.
    """
    n, hp, wp = x_padded.shape
    s = min(strip, _round8(h))
    n_strips = -(-h // s)
    win = s + _round8(2 * halo)          # aligned load: strip + its halo
    rows = (n_strips - 1) * s + win
    if rows > hp:
        x_padded = jnp.pad(x_padded, ((0, 0), (0, rows - hp), (0, 0)),
                           mode="edge")

    def kernel(x_ref, o_ref):
        start = pl.multiple_of(pl.program_id(1) * s, 8)
        o_ref[0] = body(x_ref[0, pl.ds(start, win), :], s)

    out = pl.pallas_call(
        kernel,
        grid=(n, n_strips),
        in_specs=[pl.BlockSpec((1, rows, wp), lambda i, j: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, s, w), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n_strips * s, w), jnp.float32),
        interpret=interpret,
        name=name,
    )(x_padded)
    return out[:, :h]
