"""Profiler capture and the reduction from a device trace to metrics.

``jax.profiler`` writes an ``.xplane.pb``.  Its device planes
(``/device:TPU:<n>``) carry one event per XLA operation on the line
``XLA Ops``; the host plane carries the benchmark's own spans, the
``jax.profiler.TraceAnnotation``s named ``bench.*`` that ``run.py`` and
the drivers open around their calls into the program.  Both are on one
clock.  The reduction works on the window the span ``bench.window``
covers:

* busy time per device: the union of its operations' intervals, so that
  nested or overlapping operations count once; idle share = 1 - busy /
  window, averaged over the devices;
* device time per operation name, each instant given to the operation
  that started last (so nested operations count once), averaged over
  the devices;
* idle gaps: the stretches between busy intervals, each labelled with the
  innermost ``bench.*`` span open at its middle.
"""
from __future__ import annotations

import heapq
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10


def start(log_dir) -> None:
    """Trace with the host's TraceMe spans but without Python's function
    tracer, which would add an event per Python call."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def stop(log_dir) -> Path:
    """Stop the trace; the path of the ``.xplane.pb`` it wrote."""
    import jax
    jax.profiler.stop_trace()
    paths = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"the profiler wrote no trace under {log_dir}")
    return paths[-1]


def short(op: str) -> str:
    """``%fusion.4 = f32[2073600]{...} fusion(...)`` -> ``fusion.4
    f32[2073600]``: the HLO name and its result type."""
    head, eq, rest = op.partition(" = ")
    if not eq:
        return op
    kind = rest.split(" ")[0].split("{")[0]
    return f"{head.lstrip('%')} {'tuple' if kind.startswith('(') else kind}"


def events(profile) -> dict:
    """``{"devices": {plane: [(start_ns, end_ns, op)]}, "spans": [(start_ns,
    end_ns, name)]}`` from a ``jax.profiler.ProfileData``."""
    devices: Dict[str, List[Tuple[float, float, str]]] = {}
    spans: List[Tuple[float, float, str]] = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns,
                                short(e.name)) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"devices": {k: v for k, v in devices.items() if v},
            "spans": spans}


def load(path) -> dict:
    from jax.profiler import ProfileData
    return events(ProfileData.from_file(str(path)))


def union(intervals: np.ndarray) -> np.ndarray:
    """Merge [start, end] rows into disjoint sorted intervals."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def self_times(ops: List[Tuple[float, float, str]], lo: float,
               hi: float) -> Dict[str, float]:
    """Per-name seconds inside [lo, hi]: each instant in which operations
    run goes to the one that started last (the innermost where they
    nest), so the times add up to the busy time."""
    acc: Dict[str, float] = {}
    clipped = sorted((max(s, lo), min(e, hi), n) for s, e, n in ops
                     if min(e, hi) > max(s, lo))
    bounds = sorted({t for s, e, _ in clipped for t in (s, e)})
    active: List[Tuple[float, float, str]] = []     # heap on -start
    i = 0
    for t0, t1 in zip(bounds[:-1], bounds[1:]):
        while i < len(clipped) and clipped[i][0] <= t0:
            s, e, n = clipped[i]
            heapq.heappush(active, (-s, e, n))
            i += 1
        while active and active[0][1] <= t0:
            heapq.heappop(active)
        if active:
            n = active[0][2]
            acc[n] = acc.get(n, 0.0) + (t1 - t0) * 1e-9
    return acc


def window_of(ev: dict) -> Tuple[float, float]:
    w = [(s, e) for s, e, n in ev["spans"] if n == WINDOW_SPAN]
    if w:
        return w[0]
    allv = [t for ops in ev["devices"].values() for o in ops for t in o[:2]]
    return min(allv), max(allv)


def label(spans, t: float) -> str:
    """The innermost benchmark span open at ``t``."""
    best = None
    for s, e, n in spans:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else "none"


def reduce(ev: dict, window: Tuple[float, float] = None) -> dict:
    """Busy time, idle share, operation times and idle gaps in the
    window (default: the ``bench.window`` span)."""
    lo, hi = window or window_of(ev)
    win_s = (hi - lo) * 1e-9
    if not ev["devices"]:
        raise ValueError("the trace holds no device operation")
    busy, ops, gaps = {}, {}, []
    for dev, dev_ops in sorted(ev["devices"].items()):
        iv = np.asarray([(max(s, lo), min(e, hi)) for s, e, _ in dev_ops
                         if e > lo and s < hi], np.float64).reshape(-1, 2)
        u = union(iv)
        busy[dev] = float((u[:, 1] - u[:, 0]).sum()) * 1e-9
        for name, sec in self_times(dev_ops, lo, hi).items():
            ops[name] = ops.get(name, 0.0) + sec / len(ev["devices"])
        edges = np.concatenate([[lo], u.reshape(-1), [hi]]).reshape(-1, 2)
        for s, e in edges:
            if e > s:
                gaps.append((float(e - s) * 1e-9, s, e, dev))
    spans = [sp for sp in ev["spans"] if sp[1] > lo and sp[0] < hi]
    gaps.sort(key=lambda g: -g[0])
    span_s: Dict[str, List[float]] = {}
    for s, e, n in spans:
        span_s.setdefault(n, []).append((min(e, hi) - max(s, lo)) * 1e-9)
    mean_busy = float(np.mean(list(busy.values())))
    return {
        "window_s": win_s,
        "busy_s": mean_busy,
        "busy_by_device": busy,
        "idle_share": 1.0 - mean_busy / win_s if win_s > 0 else None,
        "device_ops": [[n, s] for n, s in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[label(spans, (s + e) / 2), g]
                      for g, s, e, _ in gaps[:TOP]],
        "spans": {n: {"count": len(v), "seconds": float(sum(v))}
                  for n, v in span_s.items()},
    }
