"""One run of one cell: device gate, compile cache, driver, metric
readers, the result line.  ``run.py`` is the command; tests call
``run_cell`` with ``require_tpu=False`` to drive a run on the CPU."""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

from bench import xtrace
from bench.spec import ROOT, Bench

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# A traced run measures at most this long: the profiler's trace of a
# window grows with it (a service window of 10 s holds ~0.5M device
# operations), and reading it has to end within the run's time limit.
TRACE_SECONDS = 20.0


def log(*parts) -> None:
    print(*parts, flush=True)


@dataclasses.dataclass
class Ctx:
    """What a driver gets: the cell, the run's arguments and helpers."""
    bench: Bench
    name: str
    workload: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    clock: object
    workdir: Path
    trace_dir: Optional[Path] = None
    control: bool = False
    t_window: Optional[float] = None
    window_compiles: Optional[int] = None
    setup_compile: Optional[dict] = None
    traced: Optional[dict] = None

    def step(self, name: str, t0: float) -> float:
        """Print a set-up step's seconds since ``t0``; returns now."""
        now = time.perf_counter()
        log(f"setup {name}: {now - t0:.3f} s")
        return now

    @contextlib.contextmanager
    def window(self):
        """The measured window: starts set-up's end (``setup_s``), the
        profiler with ``--trace 1``, and the ``bench.window`` span."""
        import jax
        tdir = self.trace_dir or (self.workdir / "trace")
        if self.trace:
            xtrace.start(tdir)
        before = self.setup_compile = self.clock.snapshot()
        log(f"setup compile: {before['compile_s']:.3f} s over "
            f"{before['programs']} programs, {before['cache_hits']} from "
            f"the persistent cache")
        pauses = _gc_pauses()
        self.t_window = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(xtrace.WINDOW_SPAN):
                yield
        finally:
            gc.callbacks.remove(pauses.callback)
            log(f"window: {len(pauses)} full garbage collections, "
                f"{sum(pauses) * 1e3:.3f} ms in all, longest "
                f"{max(pauses, default=0.0) * 1e3:.3f} ms")
            after = self.clock.snapshot()
            self.window_compiles = after["programs"] - before["programs"]
            if self.trace:
                path = xtrace.stop(tdir)
                self.traced = xtrace.reduce(xtrace.load(path))
                log(f"trace: {path} ({path.stat().st_size} bytes)")
        log(f"window: {self.window_compiles} programs compiled or loaded "
            f"in the window")

    def reference(self):
        return self.bench.module("refs", self.config["reference"])


class _Pauses(list):
    callback = None


def _gc_pauses() -> _Pauses:
    """Seconds of each full (generation 2) garbage collection from now on,
    until ``callback`` is removed from ``gc.callbacks``: the interpreter
    stops every thread for it, the service's runner and the generator
    alike."""
    pauses, began = _Pauses(), []

    def callback(phase, info):
        if info["generation"] != 2:
            return
        if phase == "start":
            began.append(time.perf_counter())
        elif began:
            pauses.append(time.perf_counter() - began.pop())
    pauses.callback = callback
    gc.callbacks.append(callback)
    return pauses


def configure_cache() -> str:
    """JAX's persistent compilation cache at a fixed path:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` at the
    root of the checkout; every program is kept, however small."""
    import jax
    path = os.environ.get(CACHE_ENV) or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_record(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    rec = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"device: platform={rec['platform']} device_kind={rec['kind']} "
        f"count={rec['count']}")
    if require_tpu and rec["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX's platform is {rec['platform']!r}; "
                         f"the benchmark runs only on the chip")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks {chips} chips, JAX finds "
                         f"{len(devs)}")
    return rec


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def read_metrics(bench: Bench, cell: str, group: str, run: dict) -> dict:
    """Each applicable metric's reader; a reader that finds nothing to
    read returns None and the metric is left out."""
    out = {}
    for m in bench.metrics_for(cell, group):
        v = bench.module("metrics", m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: Optional[float] = None, bench: Optional[Bench] = None,
             require_tpu: bool = True, overrides: Optional[dict] = None,
             trace_dir=None, control: bool = False):
    """Set up, measure and check one run; returns the result object and
    the cell driver's record of the run.  ``overrides`` replaces keys of the
    cell's ``workload``, ``config`` or ``traffic`` (tests run cells at
    sizes a CPU can hold)."""
    t_start = time.perf_counter() if t_start is None else t_start
    if trace and seconds > TRACE_SECONDS:
        log(f"traced window: {TRACE_SECONDS:g} s of the {seconds:g} asked")
        seconds = TRACE_SECONDS
    bench = bench or Bench()
    cell = bench.cell(name)
    for part, keys in (overrides or {}).items():
        cell[part] = {**cell[part], **keys}
    chips = cell["workload"]["chips"]
    rec = device_record(chips, require_tpu)
    log(f"compile cache: {configure_cache()}")
    from bench.clock import CompileClock
    clock = CompileClock()
    driver = bench.module("drivers", cell["traffic"]["driver"])
    with tempfile.TemporaryDirectory(prefix="difet_bench_") as wd:
        ctx = Ctx(bench, name, cell["workload"], cell["config"],
                  cell["traffic"], int(seed), float(seconds), bool(trace),
                  t_start, clock, Path(wd),
                  Path(trace_dir) if trace_dir else None, control)
        run = driver.run(ctx)
    run["setup_s"] = ctx.t_window - ctx.t_start
    run["trace"] = ctx.traced
    run["window_compiles"] = ctx.window_compiles
    run["setup_compile"] = ctx.setup_compile
    run["chips"] = chips
    group = "per_layer" if trace else "end_to_end"
    device = {**rec, "memory_peak_bytes": run.get("memory_peak_bytes")}
    result = {"correct": bool(run["correct"]),
              "attempted": int(run["attempted"]),
              "failed": int(run["failed"]),
              "metrics": read_metrics(bench, name, group, run),
              "device": device}
    if trace:
        device["busy_s"] = ctx.traced["busy_s"]
        device["window_s"] = ctx.traced["window_s"]
        result["breakdown"] = {"device_ops": ctx.traced["device_ops"],
                               "idle_gaps": ctx.traced["idle_gaps"]}
    result["checks"] = run["checks"]
    return result, run


def main(argv, t_start: float) -> int:
    ap = argparse.ArgumentParser(description="one run of one cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler's trace here (default: a "
                         "temporary directory)")
    args = ap.parse_args(argv)
    result, _ = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start, trace_dir=args.trace_dir)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r} "
              f"(worst: {c['worst']})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
