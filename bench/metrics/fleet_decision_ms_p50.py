"""Median client-side latency of suggest_batch in a cell of many tenants
behind one server lock, where the lock's wake-up order makes it swing
from run to run (reported per layer there, end to end where it is steady)."""

from bench.metrics.decision_ms_p50 import read  # noqa: F401
