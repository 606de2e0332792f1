"""Separable Gaussian blur Pallas kernel — the SIFT scale-space hot loop.

SIFT rebuilds (n_scales+3) · n_octaves blurred images per tile (paper
Table 1: SIFT is 30-45x costlier than the other algorithms); fusing both
separable passes into one VMEM-resident kernel removes the intermediate
row-pass materialization that XLA writes back to HBM.  Computed in row
strips (`kernels/strips.py`).
"""
from __future__ import annotations

import functools

from repro.core.pyramid import gaussian_kernel_1d
from repro.kernels.strips import strip_pallas


def blur_strip(x, rows: int, *, taps, w: int):
    """x: [>= rows+2r, w+2r] -> [rows, w]."""
    r = (len(taps) - 1) // 2
    tmp = sum(float(taps[j]) * x[:, j:j + w] for j in range(2 * r + 1))
    return sum(float(taps[i]) * tmp[i:i + rows, :] for i in range(2 * r + 1))


def blur_pallas(x_padded, *, sigma: float, h: int, w: int, interpret: bool):
    taps = tuple(gaussian_kernel_1d(float(sigma)).tolist())
    return strip_pallas(functools.partial(blur_strip, taps=taps, w=w),
                        x_padded, h=h, w=w, halo=(len(taps) - 1) // 2,
                        interpret=interpret, name="gaussian_blur")
