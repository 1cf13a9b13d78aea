"""Median of (client call - server rpc.suggest_batch span), in ms: the
client library, the socket and the JSON codec on both ends."""

import statistics

from bench.metrics._spans import decision_spans


def read(run):
    ms = [((d["t1"] - d["t0"]) - rpc["dur"]) * 1e3
          for d, rpc, _ in decision_spans(run)]
    return statistics.median(ms) if ms else None
