"""The comparison that decides ``correct``: the program's answers against
the plain reference's, as three shares, each pooled over every compared
answer of one algorithm and then taken at the worst algorithm.

* ``count_gap``     sum of |program count - reference count| over the
  sum of reference counts (counts threshold the dense response map);
* ``keypoint_miss`` share of the reference's valid keypoints that the
  program's answer lacks, matched by scene position;
* ``desc_miss``     share of keypoints in both whose descriptors differ:
  any bit of a binary descriptor, or an L2 distance over ``DESC_TOL``
  between unit-norm float descriptors.

The limits are the configuration's (``limits`` in its file)."""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

import numpy as np

NUMBERS = ("count_gap", "keypoint_miss", "desc_miss")
DESC_TOL = 1e-3


def _keyed(res) -> dict:
    valid = np.asarray(res["top_valid"], bool)
    ys, xs = np.asarray(res["top_ys"]), np.asarray(res["top_xs"])
    desc = res.get("top_desc")
    return {(int(ys[i]), int(xs[i])): (None if desc is None
                                       else np.asarray(desc[i]))
            for i in np.flatnonzero(valid)}


class Tally:
    """Pooled counts per algorithm over the compared answers."""

    def __init__(self):
        self.t: Dict[str, np.ndarray] = {}

    def add(self, alg: str, got: dict, want: dict) -> None:
        g, w = _keyed(got), _keyed(want)
        both = [k for k in w if k in g and w[k] is not None]
        dmiss = sum(_differ(g[k], w[k]) for k in both)
        row = np.array([abs(int(got["total_count"])
                            - int(want["total_count"])),
                        int(want["total_count"]),
                        sum(k not in g for k in w), len(w),
                        dmiss, len(both)], np.int64)
        self.t[alg] = self.t.get(alg, 0) + row

    def keypoints(self) -> int:
        """Reference keypoints compared, over every algorithm."""
        return int(sum(r[3] for r in self.t.values()))

    def numbers(self) -> Dict[str, Tuple[float, str]]:
        """``{number: (worst value, its algorithm)}``."""
        out = {}
        for j, name in enumerate(NUMBERS):
            worst = (0.0, "")
            for alg, r in sorted(self.t.items()):
                v = float(r[2 * j]) / max(float(r[2 * j + 1]), 1.0)
                if v > worst[0] or not worst[1]:
                    worst = (v, alg)
            out[name] = worst
        return out


def _differ(a, b) -> bool:
    if b.dtype.kind in "ui":
        return bool(np.any(a != b))
    return bool(np.linalg.norm(a.astype(np.float64) - b.astype(np.float64))
                > DESC_TOL)


def checks(numbers: dict, limits: dict) -> Tuple[bool, dict]:
    """``(all within limits, {name: {value, limit, algorithm}})``, in the
    order of ``NUMBERS``."""
    out, ok = {}, True
    for name in NUMBERS:
        v, alg = numbers[name]
        lim = float(limits[name])
        ok &= bool(v <= lim)
        out[name] = {"value": v, "limit": lim, "worst": alg}
    return ok, out


def tally(pairs: Iterable) -> Tally:
    """A tally over ``(alg, got, want)`` triples."""
    t = Tally()
    for alg, got, want in pairs:
        t.add(alg, got, want)
    return t
