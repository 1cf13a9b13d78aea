"""Median, in ms, of the wait for the server's lock before a decision: the
server.lock_wait span under each of the window's rpc.suggest_batch spans.
A program without that span reads nothing."""

import statistics

from bench.metrics._spans import by_name


def lock_waits(run):
    """(rpc.suggest_batch span, its server.lock_wait child) of each decision
    of the window."""
    rpcs = {s["span_id"]: s for s in by_name(run, "rpc.suggest_batch")}
    return [(rpcs[w["parent_id"]], w) for w in by_name(run, "server.lock_wait")
            if w["parent_id"] in rpcs]


def read(run):
    ms = [w["dur"] * 1e3 for _, w in lock_waits(run)]
    return statistics.median(ms) if ms else None
