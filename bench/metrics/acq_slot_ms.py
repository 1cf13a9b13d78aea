"""Median per decision slot of suggest.acq_opt + suggest.dedup, in ms.

optimize_acquisition is dispatched asynchronously: acq_opt times the
enqueue and dedup's read of the candidates waits for the device, so only
their sum is the slot's acquisition time."""

import statistics
from collections import defaultdict

from bench.metrics._spans import by_name


def read(run):
    slots = defaultdict(float)
    for name in ("suggest.acq_opt", "suggest.dedup"):
        for s in by_name(run, name):
            slots[(s["parent_id"], s["attrs"].get("slot"))] += s["dur"]
    return statistics.median(slots.values()) * 1e3 if slots else None
