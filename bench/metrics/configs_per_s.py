"""Configurations returned inside the window over its seconds: those of the
decisions that returned by the deadline, from the window's opening to the
deadline. The clients ask for no decision after the deadline; those still
in flight then return after it and are not counted, so the rate does not
swing with how long the last of them takes."""


def read(run):
    t0, _ = run.window
    n = sum(len(d["configs"]) for d in run.decisions
            if d["t1"] <= run.deadline)
    return n / (run.deadline - t0) if run.deadline > t0 else None
