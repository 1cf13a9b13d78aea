"""Configurations returned by the window's decisions over the window's
seconds, from its opening until the last of them returned."""


def read(run):
    t0, t1 = run.window
    n = sum(len(d["configs"]) for d in run.decisions)
    return n / (t1 - t0) if t1 > t0 else None
