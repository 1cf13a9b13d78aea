"""Median, in ms, of how long one decision holds the server's lock (and so
the replica): rpc.suggest_batch less its server.lock_wait child."""

import statistics

from bench.metrics.lock_queue_ms import lock_waits


def read(run):
    ms = [(rpc["dur"] - w["dur"]) * 1e3 for rpc, w in lock_waits(run)]
    return statistics.median(ms) if ms else None
