"""Share of the traced window, in %, in which no operation ran on the
chip: 1 - (union of busy intervals) / window."""


def read(run):
    tr = run.trace
    if tr is None or not tr["chips_traced"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
