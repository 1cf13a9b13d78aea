"""Median client-side latency of suggest_batch over the window's decisions."""

import statistics


def read(run):
    ms = [(d["t1"] - d["t0"]) * 1e3 for d in run.decisions if "error" not in d]
    return statistics.median(ms) if ms else None
