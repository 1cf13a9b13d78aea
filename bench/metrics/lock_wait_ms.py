"""Median of (rpc.suggest_batch - service.suggest_batch), in ms: the wait
for the server's lock (and its lease checks) before the service decides."""

import statistics

from bench.metrics._spans import decision_spans


def read(run):
    ms = [(rpc["dur"] - s["dur"]) * 1e3 for _, rpc, s in decision_spans(run)]
    return statistics.median(ms) if ms else None
