"""FAST-N segment-test score Pallas kernel.

Hardware adaptation (DESIGN.md §5): the CPU/OpenCV FAST is branchy (early
exit on the 1-5-9-13 probe); on the TPU VPU it is re-formulated
branch-free (`core/detectors.py::fast_from_padded`, shared with the jnp
path): the 16 circle neighbours are shifted VMEM slices, the "contiguous
arc of length >= N" test is an OR over 16 start positions of an AND over
the arc (built by run doubling: log2 N ANDs per start, not N - 1), and
the score is a masked sum.  One HBM read per tile, computed in row strips
(`kernels/strips.py`): unrolled over a whole 560² tile the segment test
made a program the TPU compiler did not finish in minutes.
"""
from __future__ import annotations

import functools

from repro.core.detectors import fast_from_padded
from repro.kernels.strips import strip_pallas


def fast_pallas(x_padded, *, threshold: float, arc: int, h: int, w: int,
                interpret: bool):
    body = functools.partial(fast_from_padded, w=w, threshold=threshold,
                             arc=arc)
    return strip_pallas(body, x_padded, h=h, w=w, halo=3,
                        interpret=interpret, name="fast_score")
