"""Open-loop request schedules for the tile service, from the seed.

Built on the program's trace generator (``serve/trace.py``): Poisson,
uniform or burst arrivals, hot-scene skew and an algorithm-set mix.  One
change: every seed gets the same work in another order.  A schedule of
``seconds`` at ``rate`` holds exactly ``round(rate * seconds)`` requests,
each algorithm set and the hot set get their exact shares, the gaps
between arrivals are one fixed set, and only the order and the tiles move
with the seed, so that runs of different seeds are as alike as runs of
one."""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    t: float                       # seconds from the schedule's start
    tile: int                      # index into the cell's tile pool
    algorithms: Tuple[str, ...]


def _exact_counts(n: int, weights) -> np.ndarray:
    """Largest-remainder split of ``n`` by ``weights``."""
    w = np.asarray(weights, np.float64)
    raw = n * w / w.sum()
    out = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - out), kind="stable")[:n - out.sum()]:
        out[i] += 1
    return out


def arrivals(n: int, seconds: float, kind: str, rng, burst_factor=4.0,
             burst_fraction=0.25) -> np.ndarray:
    """``n`` arrival offsets in ``[0, seconds)``.  The gaps are drawn once
    from a fixed stream, so every seed gets the same set of gaps; ``rng``
    (the seed's) only puts them in its own order (for bursts, whole runs
    of 8 at a time).  Scaled so the schedule spans exactly ``seconds``."""
    fixed = np.random.RandomState(0)
    mean = seconds / n
    if kind == "uniform":
        gaps = np.full(n, mean)
    elif kind == "poisson":
        gaps = fixed.exponential(mean, n)
        rng.shuffle(gaps)
    elif kind == "burst":
        # as serve/trace.py: runs of 8 requests at burst_factor x the rate
        f, k = burst_fraction, burst_factor
        calm = mean * (1.0 - f / k) / max(1.0 - f, 1e-9)
        spike = fixed.rand(n) < f
        gaps = np.where(spike, mean / k, calm)
        for i in range(0, n - 8, 8):
            if spike[i]:
                gaps[i:i + 8] = mean / k
        runs = [gaps[i:i + 8] for i in range(0, n, 8)]
        gaps = np.concatenate([runs[i] for i in rng.permutation(len(runs))])
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return t * (seconds / (t[-1] + gaps[-1]))


def schedule(traffic: dict, seconds: float, pool_size: int, rng,
             first_tile: int = 0) -> List[Request]:
    """One schedule of the cell's mix.  With ``hot_weight`` set, that
    share of requests goes to the first ``hot_fraction`` of the pool;
    otherwise every request gets a tile of its own, from ``first_tile``
    on (so a pool larger than all requests never repeats one)."""
    n = max(1, int(round(traffic["rate"] * seconds)))
    t = arrivals(n, seconds, traffic["arrival"], rng,
                 traffic.get("burst_factor", 4.0),
                 traffic.get("burst_fraction", 0.25))
    sets = [tuple(s) for s in traffic["algorithm_sets"]]
    set_idx = np.repeat(np.arange(len(sets)),
                        _exact_counts(n, traffic["algorithm_weights"]))
    rng.shuffle(set_idx)
    if "hot_weight" in traffic:
        n_hot = max(1, int(np.ceil(traffic["hot_fraction"] * pool_size)))
        k_hot = _exact_counts(n, [traffic["hot_weight"],
                                  1 - traffic["hot_weight"]])[0]
        tiles = np.concatenate([rng.randint(0, n_hot, k_hot),
                                rng.randint(n_hot, pool_size, n - k_hot)])
        rng.shuffle(tiles)
    else:
        if first_tile + n > pool_size:
            raise ValueError(f"pool of {pool_size} tiles cannot give "
                             f"{first_tile + n} requests a tile each")
        tiles = first_tile + rng.permutation(n)
    return [Request(float(t[i]), int(tiles[i]), sets[set_idx[i]])
            for i in range(n)]


class WindowPool:
    """A pool of distinct ``side`` x ``side`` tiles: windows of one
    synthetic scene at distinct offsets on a ``stride`` grid, drawn from
    the seed.  One scene in bulk instead of a scene per tile keeps the
    set-up short; each window's bytes differ, so the service's content
    hash sees every tile as new."""

    def __init__(self, scene: np.ndarray, side: int, size: int,
                 stride: int, rng):
        h, w = scene.shape
        ny, nx = (h - side) // stride + 1, (w - side) // stride + 1
        if size > ny * nx:
            raise ValueError(f"a {h}x{w} scene holds {ny * nx} windows, "
                             f"not {size}")
        pick = rng.choice(ny * nx, size, replace=False)
        self.offsets = np.stack([pick // nx * stride, pick % nx * stride], 1)
        self.scene, self.side = scene, side

    def __len__(self):
        return len(self.offsets)

    def tile(self, i: int) -> np.ndarray:
        y, x = self.offsets[i]
        return self.scene[y:y + self.side, x:x + self.side]
