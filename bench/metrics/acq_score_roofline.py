"""Share of its roofline, in %, that the acq_score kernel reached in the
traced window: the chip's least time for the calls' work (bench/work/
acq_score.py, bench/peaks.json) over the device time of their events.

Each optimize_acquisition call is annotated on the host with its shapes;
a kernel event belongs to the last such annotation that began before it
(the server's lock serializes decisions, and each waits for its results).
Only calls followed by another inside the window count, so that each
holds all its kernel events."""

from bench.work import acq_score as work


def _shape(label):
    fields = dict(kv.split("=") for kv in label.split()[1:])
    return {"s": int(fields["s"]), "n": int(fields["n"]), "d": int(fields["d"]),
            "num_anchors": int(fields["a"]), "num_refine": int(fields["r"])}


def read(run):
    tr = run.trace
    if tr is None or not tr["chips_traced"] or run.peaks is None:
        return None
    calls = sorted((s, label) for label, s, _ in tr["annotations"]
                   if label.startswith("bench.acq_opt "))
    events = sorted(tr["kernel_events"]["acq_score"])
    least = busy = 0.0
    for (start, label), (end, _) in zip(calls, calls[1:]):
        mine = [dur for s, dur in events if start <= s < end]
        expect = work.calls(_shape(label))
        if len(mine) != len(expect):
            continue
        least += sum(work.least_seconds(c, run.peaks) for c in expect)
        busy += sum(mine) * 1e-9
    return 100.0 * least / busy if busy > 0 else None
