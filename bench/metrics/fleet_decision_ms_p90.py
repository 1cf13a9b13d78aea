"""90th percentile of client-side suggest_batch latency over the window's
decisions (linear interpolation between order statistics): the tail a
tenant sees behind the server lock."""

import numpy as np


def read(run):
    ms = [(d["t1"] - d["t0"]) * 1e3 for d in run.decisions if "error" not in d]
    return float(np.percentile(ms, 90)) if ms else None
