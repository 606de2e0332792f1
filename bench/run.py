"""Run one cell of DIFET's on-chip benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Sets up the cell named in ``BENCHMARK.json`` (its configuration, traffic
mix and driver are files under ``bench/``, found by name), measures for
``--seconds`` (a traced run for 20 s at most), checks what the timed path produced against the plain
reference, and prints one JSON line last on stdout: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number beside its
limit, which also ends stderr.  Runs only on a TPU: without one, or with
fewer chips than the cell asks, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()     # set-up is timed from process start

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

if __name__ == "__main__":
    _root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_root), str(_root / "src")]
    from bench import harness
    sys.exit(harness.main(sys.argv[1:], T_START))
