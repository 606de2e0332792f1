"""The program's own spans and named scopes in a device trace, and a tool
that runs one cell traced and prints what they show.

    python3 bench/program_trace.py --workload xyz256.steady --seed 7 \
        --seconds 20 --out runs/steady_trace.json

The benchmark's reduction (``xtrace.py``) reads the benchmark's own
``bench.*`` spans alone.  This module reads the same trace with the
program's instrumentation as well, both on the device's clock:

* the program's spans, ``jax.profiler`` TraceMe events named
  ``difet.<layer>.<name>`` (``obs/trace.py::span``), at the layer
  boundaries inside the job (``difet.job.*``), the scheduler
  (``difet.scheduler.idle``, ``.fill``) and the serve runner
  (``difet.batch.scatter``, ``difet.kernel.device_step``,
  ``difet.batch.deliver``);
* the ``jax.named_scope`` paths of ``core/engine.py``
  (``difet.response.<fn>``, and ``difet.<algorithm>/<stage>`` for
  ``nms``, ``topk``, ``describe`` and ``reduce``).  A TPU's ``XLA Ops``
  events carry no such path (their stats are ``device_offset_ps``,
  ``device_duration_ps`` and ``Time Scale Multiplier``, read on a v5e),
  so each operation is mapped to its scope through the compiled
  program's HLO text, where every instruction carries its ``op_name``
  metadata (``op_scopes``).

``reduce`` adds to ``xtrace.reduce``'s output, whose keys it leaves as
they are except the labels of ``idle_gaps``:

* ``idle_gaps`` labelled with the innermost program span open at the
  gap's middle, failing that the innermost benchmark span, failing that
  ``none`` (a trace without program spans keeps ``xtrace``'s labels);
* ``idle_by_span``: idle seconds (mean over the devices) by that label,
  at every idle instant;
* ``scopes``: device seconds by named scope at the ``difet.<x>/<stage>``
  level, summed over the devices, each instant given to the operation
  that owns it (``xtrace.self_times``); unscoped time goes under
  ``other``, so they add up to the sum of ``busy_by_device``;
* ``op_scopes``: the scope of each of ``device_ops``.

The tool runs a cell once with ``--trace 1`` in its own process (the
benchmark's ``harness.run_cell``), keeps the profiler's trace, compiles
(or loads from the compile cache) the cell's programs as its driver
builds them for their HLO text (``cell_hlo``), and prints one JSON line:
the run's result, this reduction, and ``numbers``, the
per-layer quantities that the program's instrumentation gives (see
``numbers``).  None of them is a metric of ``BENCHMARK.json``: reading
them there takes the benchmark's reduction and the service driver's
window counters to carry them."""
from __future__ import annotations

import bisect
import heapq
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

if __name__ == "__main__":
    _root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_root), str(_root / "src")]

from bench import xtrace  # noqa: E402

PROGRAM_PREFIX = "difet."
OTHER = "other"
DESCRIBE = "/describe"


def scope_of(path: str) -> str:
    """A ``jax.named_scope`` path's ``difet.<x>/<stage>`` level:
    ``jit(f)/shard_map/vmap(difet.sift)/describe/jit(_take)/gather`` ->
    ``difet.sift/describe``; ``.../vmap(difet.response.fast)/...`` ->
    ``difet.response.fast``; ``other`` where no ``difet.`` scope is on the
    path."""
    parts = [p[p.index("(") + 1:-1] if p.endswith(")") and "(" in p else p
             for p in path.split("/")]
    for i, p in enumerate(parts):
        if p.startswith(PROGRAM_PREFIX):
            if p.startswith(PROGRAM_PREFIX + "response.") \
                    or i + 1 == len(parts):
                return p
            return f"{p}/{parts[i + 1]}"
    return OTHER


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """``{op: scope}`` from a compiled program's HLO text, ``op`` named as
    ``xtrace.short`` names a trace's operation (``fusion.56
    f32[8294400]``), for every instruction whose ``op_name`` metadata
    holds a ``difet.`` scope."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        head, _, meta = line.partition('op_name="')
        if not meta or " = " not in head:
            continue
        scope = scope_of(meta.split('"', 1)[0])
        if scope != OTHER:
            out[xtrace.short(head.strip().removeprefix("ROOT "))] = scope
    return out


def events(profile, scopes: Optional[Dict[str, str]] = None) -> dict:
    """``xtrace.events`` with the program's spans among ``spans``, and
    ``scopes`` (``{op: scope}``, from ``op_scopes``) carried along."""
    ev = xtrace.events(profile)
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                ev["spans"].extend(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events
                    if e.name.startswith(PROGRAM_PREFIX))
    ev["scopes"] = dict(scopes or {})
    return ev


def load(path, scopes: Optional[Dict[str, str]] = None) -> dict:
    from jax.profiler import ProfileData
    return events(ProfileData.from_file(str(path)), scopes)


def cell_hlo(bench, cell: dict) -> List[str]:
    """Compiled HLO text of the programs a cell's window runs (``cell`` as
    ``Bench.cell`` gives it), built as its driver builds them: the job's
    sharded program at one bundle shard's shape on the cell's mesh, or
    the service's step per algorithm set at its bucket and batch shape.
    Each is compiled afresh, past JAX's caches: the persistent cache keys
    a program without its op metadata, so an executable loaded from it
    may carry the metadata of another build of the same program (on a
    v5e, one built before the named scopes existed); compiling the same
    program again gives the same instruction names."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from repro.configs.difet_paper import DifetConfig

    c, tr = cell["config"], cell["traffic"]
    keys = bench.module("drivers", tr["driver"]).DIFET_KEYS

    def text(fn, n, hw):
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        jax.clear_caches()
        try:
            return fn.lower(jax.ShapeDtypeStruct((n, hw, hw), jnp.float32),
                            jax.ShapeDtypeStruct((n, 6), jnp.int32)
                            ).compile().as_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()

    if tr["driver"] == "job":
        import tempfile
        from repro.core.bundle import BundleStore
        from repro.core.job import DifetJob
        from repro.distributed.sharding import data_mesh
        cfg = DifetConfig(**{k: tuple(c[k]) if isinstance(c[k], list)
                             else c[k] for k in keys})
        chips = cell["workload"]["chips"]
        n = -(-c["bundle_tiles"] // c["shards_per_bundle"])
        n += (-n) % chips
        with tempfile.TemporaryDirectory() as td:
            job = DifetJob(BundleStore(td), ",".join(tr["algorithms"]),
                           mesh=data_mesh(chips),
                           manifest_path=Path(td) / "m.json")
            hw = c["tile"] + 2 * c["halo"]
            return [text(job._sharded_fn((n, hw, hw), cfg), n, hw)]
    from repro.core.engine import make_serve_step, normalize_algorithms
    from repro.serve.buckets import BucketTable
    (bucket,) = c["buckets"]
    table = BucketTable((bucket,), DifetConfig(
        tile=bucket, **{k: c[k] for k in keys}))
    hw = bucket + 2 * table.halo
    return [text(make_serve_step(tuple(sorted(normalize_algorithms(a))),
                                 table.cfg_for(bucket),
                                 use_pallas=c["use_pallas"]),
                 c["max_batch"], hw)
            for a in tr["algorithm_sets"]]


def _rank(s, e, n) -> tuple:
    """Program spans outrank benchmark spans; then the shorter wins."""
    return (not n.startswith(PROGRAM_PREFIX), e - s)


def segments(spans, lo: float, hi: float) -> List[Tuple[float, float, str]]:
    """``[lo, hi]`` cut where a span opens or closes, each piece labelled
    with the innermost program span open in it, failing that the
    innermost benchmark span, failing that ``none``; neighbours with one
    label are joined."""
    # of equal rank, the span listed first wins, as in ``xtrace.label``
    ranked = sorted((s, (_rank(s, e, n), i), e, n)
                    for i, (s, e, n) in enumerate(spans))
    bounds = sorted({lo, hi} | {min(max(t, lo), hi)
                                for s, _, e, _ in ranked for t in (s, e)})
    out: List[Tuple[float, float, str]] = []
    active: list = []                     # heap on (rank, index)
    i = 0
    for t0, t1 in zip(bounds[:-1], bounds[1:]):
        while i < len(ranked) and ranked[i][0] <= t0:
            _, key, e, n = ranked[i]
            heapq.heappush(active, (key, e, n))
            i += 1
        while active and active[0][1] <= t0:
            heapq.heappop(active)
        name = active[0][2] if active else "none"
        if out and out[-1][2] == name:
            out[-1] = (out[-1][0], t1, name)
        else:
            out.append((t0, t1, name))
    return out


def _overlap_by_label(gaps, segs) -> Dict[str, float]:
    """Seconds of each label's segments inside the sorted, disjoint
    ``gaps``."""
    acc: Dict[str, float] = {}
    j = 0
    for gs, ge in gaps:
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < ge:
            s, e, n = segs[k]
            acc[n] = acc.get(n, 0.0) + (min(e, ge) - max(s, gs)) * 1e-9
            k += 1
    return acc


def _by_value(d: Dict[str, float]) -> Dict[str, float]:
    return dict(sorted(d.items(), key=lambda kv: -kv[1]))


def reduce(ev: dict, window: Optional[Tuple[float, float]] = None) -> dict:
    """``xtrace.reduce`` plus ``scopes``, ``idle_by_span`` and
    ``op_scopes``, with ``idle_gaps`` labelled program span first."""
    lo, hi = window or xtrace.window_of(ev)
    out = xtrace.reduce(ev, (lo, hi))
    spans = [sp for sp in ev["spans"] if sp[1] > lo and sp[0] < hi]
    segs = segments(spans, lo, hi)
    n_dev = len(ev["devices"])
    op_scope = ev.get("scopes", {})
    scopes: Dict[str, float] = {}
    idle_by: Dict[str, float] = {}
    gaps = []
    for dev, dev_ops in sorted(ev["devices"].items()):
        iv = np.asarray([(max(s, lo), min(e, hi)) for s, e, _ in dev_ops
                         if e > lo and s < hi], np.float64).reshape(-1, 2)
        u = xtrace.union(iv)
        for name, sec in xtrace.self_times(dev_ops, lo, hi).items():
            sc = op_scope.get(name, OTHER)
            scopes[sc] = scopes.get(sc, 0.0) + sec
        edges = np.concatenate([[lo], u.reshape(-1), [hi]]).reshape(-1, 2)
        idle = [(s, e) for s, e in edges if e > s]
        gaps += [(float(e - s) * 1e-9, s, e) for s, e in idle]
        for n, sec in _overlap_by_label(idle, segs).items():
            idle_by[n] = idle_by.get(n, 0.0) + sec / n_dev
    gaps.sort(key=lambda g: -g[0])
    starts = [sg[0] for sg in segs]
    out["idle_gaps"] = [
        [segs[bisect.bisect_right(starts, (s + e) / 2) - 1][2], g]
        for g, s, e in gaps[:xtrace.TOP]]
    out["scopes"] = _by_value(scopes)
    out["idle_by_span"] = _by_value(idle_by)
    out["op_scopes"] = {n: op_scope.get(n, OTHER)
                        for n, _ in out["device_ops"]}
    return out


def _span_s(red: dict, name: str) -> float:
    return red["spans"].get(name, {}).get("seconds", 0.0)


def numbers(run: dict, red: dict) -> Dict[str, Optional[float]]:
    """Per-layer quantities of one traced run (the driver's record ``run``
    and this module's reduction ``red`` of its window), each None where
    its input is missing:

    * ``queue_wait_ms.tile``: the scheduler's ``wait_s`` over ``items``
      of the window (enqueue to batch formation);
    * ``runner_host_ms_per_step.tile``: ``difet.batch.scatter`` plus
      ``difet.batch.deliver`` seconds per serve step;
    * ``describe_ms_per_step.tile``: device seconds under
      ``difet.<algorithm>/describe`` per serve step;
    * ``describe_ms_per_tile.batch``: the same, summed over the chips,
      per committed tile;
    * ``job_host_ms_per_bundle.batch``: ``difet.job.bundle`` less
      ``difet.job.fetch`` seconds per committed bundle;
    * ``idle_under_program_share``: the share of device idle time under
      a program span."""
    st = run.get("stats") or {}
    steps = st.get("batches")
    describe = [v for k, v in red["scopes"].items() if k.endswith(DESCRIBE)]
    host = [n for n in ("difet.batch.scatter", "difet.batch.deliver")
            if n in red["spans"]]
    idle = sum(red["idle_by_span"].values())
    return {
        "queue_wait_ms.tile": (st["wait_s"] / st["items"] * 1e3
                               if "wait_s" in st and st.get("items")
                               else None),
        "runner_host_ms_per_step.tile": (
            sum(_span_s(red, n) for n in host) / steps * 1e3
            if steps and host else None),
        "describe_ms_per_step.tile": (sum(describe) / steps * 1e3
                                      if steps and describe else None),
        "describe_ms_per_tile.batch": (
            sum(describe) / run["tiles"] * 1e3
            if run.get("tiles") and describe else None),
        "job_host_ms_per_bundle.batch": (
            (_span_s(red, "difet.job.bundle")
             - _span_s(red, "difet.job.fetch")) / run["commits"] * 1e3
            if run.get("commits") and "difet.job.bundle" in red["spans"]
            else None),
        "idle_under_program_share": (
            sum(v for k, v in red["idle_by_span"].items()
                if k.startswith(PROGRAM_PREFIX)) / idle if idle else None),
    }


def _carry_wait(bench) -> None:
    """The service driver's window counters with the scheduler's
    ``wait_s`` difference added (the driver's ``_delta`` carries a fixed
    set of counters)."""
    drv = bench.module("drivers", "service")
    delta = drv._delta

    def with_wait(a, b):
        out = delta(a, b)
        if "wait_s" in b["scheduler"]:
            out["wait_s"] = b["scheduler"]["wait_s"] - a["scheduler"]["wait_s"]
        return out
    drv._delta = with_wait


def main(argv=None) -> int:
    import argparse
    import json
    import tempfile

    from bench import harness
    from bench.spec import Bench

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=harness.TRACE_SECONDS)
    ap.add_argument("--out", default=None, help="also write the line here")
    args = ap.parse_args(argv)
    bench = Bench()
    _carry_wait(bench)
    with tempfile.TemporaryDirectory(prefix="difet_ptrace_") as td:
        result, run = harness.run_cell(args.workload, args.seed,
                                       args.seconds, True, bench=bench,
                                       trace_dir=td)
        (path,) = sorted(Path(td).glob("plugins/profile/*/*.xplane.pb"))[-1:]
        scopes: Dict[str, str] = {}
        for hlo in cell_hlo(bench, bench.cell(args.workload)):
            scopes.update(op_scopes(hlo))
            print(f"program: {hlo.count('op_name=')} instructions with "
                  f"op_name, {len(scopes)} ops in difet. scopes",
                  file=sys.stderr, flush=True)
        red = reduce(load(path, scopes))
    line = {"workload": args.workload, "seed": args.seed,
            "correct": result["correct"], "metrics": result["metrics"],
            "device": result["device"], "numbers": numbers(run, red),
            "window": {k: red[k] for k in ("window_s", "busy_s",
                                           "idle_share")},
            "scopes": red["scopes"], "idle_by_span": red["idle_by_span"],
            "idle_gaps": red["idle_gaps"], "device_ops": red["device_ops"],
            "op_scopes": red["op_scopes"],
            "spans": red["spans"], "stats": run.get("stats")}
    text = json.dumps(line)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
