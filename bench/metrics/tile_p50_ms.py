"""Median latency of every request due in the window, from its due time
to its answer; a missing answer waits the longest."""
import numpy as np


def read(run):
    if "latency_s" not in run:
        return None
    return float(np.percentile(run["latency_s"], 50)) * 1e3
