"""How unevenly the replicas served the window, in %: 100 x (the most
decisions any one replica answered / the mean over the replicas - 1), from
the clients' records of which replica answered each decision (0 for a
single replica)."""

import collections


def read(run):
    counts = collections.Counter(d["replica"] for d in run.decisions
                                 if "error" not in d)
    if not counts:
        return None
    mean = sum(counts.values()) / run.replicas
    return 100.0 * (max(counts.values()) / mean - 1.0)
