"""Fused structure-tensor corner kernel (Harris / Shi-Tomasi).

The jnp reference makes 7 HBM round-trips per tile (2 sobel maps, 3 product
maps, 3 blurred maps, response); this kernel does ONE: the padded tile is
DMA'd to VMEM, and gradients → products → separable Gaussian window →
response are all computed on VMEM values.

Grid: one padded tile per DMA, computed in row strips
(`kernels/strips.py`) so each program's working set is a strip's, not a
whole 560² tile's.  The lane dim (W) is padded to a 128 multiple by the
caller (ops.py) so the VPU sees aligned vectors.

Gaussian taps are compile-time constants (sigma is static per pallas_call),
so the separable window unrolls into 2·(2r+1) fused multiply-adds.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.core.pyramid import gaussian_kernel_1d
from repro.kernels.strips import strip_pallas


def _sobel_vmem(x, h, w):
    """Sobel gradients of the (h+2, w+2)-padded VMEM value x -> (h, w)."""
    sl = lambda dy, dx: x[1 + dy:1 + dy + h, 1 + dx:1 + dx + w]
    gx = (sl(-1, 1) + 2 * sl(0, 1) + sl(1, 1)
          - sl(-1, -1) - 2 * sl(0, -1) - sl(1, -1)) / 8.0
    gy = (sl(1, -1) + 2 * sl(1, 0) + sl(1, 1)
          - sl(-1, -1) - 2 * sl(-1, 0) - sl(-1, 1)) / 8.0
    return gx, gy


def _blur_vmem(x, taps, h, w):
    """Separable blur of the (h+2r, w+2r)-padded VMEM value -> (h, w)."""
    r = (len(taps) - 1) // 2
    tmp = sum(float(taps[j]) * x[:, j:j + w] for j in range(2 * r + 1))
    return sum(float(taps[i]) * tmp[i:i + h, :] for i in range(2 * r + 1))


def harris_strip(x, rows: int, *, k: float, taps, shi_tomasi: bool, w: int):
    """x: [>= rows + 2*(r+1), w + 2*(r+1)] -> response [rows, w]."""
    r = (len(taps) - 1) // 2
    # gradients on the blur-padded extent (valid for blurring afterwards)
    gx, gy = _sobel_vmem(x, rows + 2 * r, w + 2 * r)
    ixx = _blur_vmem(gx * gx, taps, rows, w)
    iyy = _blur_vmem(gy * gy, taps, rows, w)
    ixy = _blur_vmem(gx * gy, taps, rows, w)
    if shi_tomasi:
        half_tr = 0.5 * (ixx + iyy)
        rad = jnp.sqrt(jnp.maximum(0.25 * (ixx - iyy) ** 2 + ixy * ixy, 0.0))
        return half_tr - rad
    det = ixx * iyy - ixy * ixy
    tr = ixx + iyy
    return det - k * tr * tr


def harris_pallas(x_padded, *, k: float, sigma: float, shi_tomasi: bool,
                  h: int, w: int, interpret: bool):
    """x_padded: [n, h+2p, w+2p] with p = blur_radius + 1."""
    taps = tuple(gaussian_kernel_1d(float(sigma)).tolist())
    body = functools.partial(harris_strip, k=k, taps=taps,
                             shi_tomasi=shi_tomasi, w=w)
    return strip_pallas(body, x_padded, h=h, w=w, halo=(len(taps) + 1) // 2,
                        interpret=interpret,
                        name="shi_tomasi" if shi_tomasi else "harris")
