"""The profiler trace of a run: capture, load, and reduce to device metrics.

``load`` turns the profiler's ``.xplane.pb`` into plain data,
``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
dur_ns], ...]}]}]}``, and ``reduce`` works on that form only, so it can be
checked on a small recorded trace without a chip.

Within the traced window (the host annotation ``bench.window``):

* busy: the union of the intervals of every event on a chip's plane,
  averaged over the chips;
* device ops: the device seconds of each program (``XLA Modules`` line)
  and of each kernel;
* kernel events: the ``XLA Ops`` events whose HLO instruction is one of a
  kernel's trace names (``%acq_score_pallas.3 = ...`` is ``acq_score``);
* annotations: the harness's own host annotations (``bench.*``) that
  begin inside the window;
* idle gaps: each stretch of ``GAP_NS`` or more with nothing on the chip,
  named by the host event that covers at least half of it and is the
  shortest such (else the one that overlaps it most), summed by name.

A chip whose trace buffer fills stops recording ops and marks the rest of
the window with one event on its ``XLA TraceMe`` line (seen on a v5e, at
about six million op events): the window then ends where that mark
begins, and the mark is no device work.
"""

from __future__ import annotations

import glob
import re
from collections import defaultdict
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STOPPED_LINE = "XLA TraceMe"
GAP_NS = 100_000  # shorter gaps are launch overhead, summed apart
SHORT_GAPS = "gaps under 100 us"
HOST_MIN_NS = 1_000  # host events shorter than this name no gap
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def load(logdir: str) -> Dict[str, Any]:
    """The device and host planes of the trace under ``logdir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[0])
    planes = []
    for plane in data.planes:
        if not (_DEVICE.match(plane.name) or plane.name == "/host:CPU"):
            continue
        lines = []
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _window(trace) -> Tuple[int, int]:
    for plane in trace["planes"]:
        if _DEVICE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW:
                    return start, start + dur
    raise ValueError(f"the trace holds no {WINDOW!r} annotation")


def _device_lines(trace, chips: int) -> Dict[int, Dict[str, list]]:
    """Each chip's lines: chip -> {line name: [(name, start, end)]}."""
    out: Dict[int, Dict[str, list]] = {}
    for plane in trace["planes"]:
        m = _DEVICE.match(plane["name"])
        if not m or int(m.group(1)) >= chips:
            continue
        lines = out.setdefault(int(m.group(1)), {})
        for ln in plane["lines"]:
            lines.setdefault(ln["name"], []).extend(
                (n, s, s + d) for n, s, d in ln["events"] if d > 0)
    return out


def op_name(event: str) -> str:
    """The HLO instruction's name without its ``%`` and ``.N`` suffix."""
    return event.split(" = ", 1)[0].lstrip("%").split(".", 1)[0]


def module_name(event: str) -> str:
    return event.split("(", 1)[0]


def _host_events(trace, t0: int, t1: int):
    names, starts, ends = [], [], []
    for plane in trace["planes"]:
        if _DEVICE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                if name != WINDOW and d >= HOST_MIN_NS and s < t1 and s + d > t0:
                    names.append(name)
                    starts.append(s)
                    ends.append(s + d)
    return names, np.asarray(starts, np.int64), np.asarray(ends, np.int64)


def _attribute(gap, names, starts, ends) -> str:
    g0, g1 = gap
    if not names:
        return "no host event"
    overlap = np.clip(np.minimum(ends, g1) - np.maximum(starts, g0), 0, None)
    if overlap.max() <= 0:
        return "no host event"
    half = overlap * 2 >= (g1 - g0)
    if half.any():
        length = np.where(half, ends - starts, np.iinfo(np.int64).max)
        return names[int(np.argmin(length))]
    return names[int(np.argmax(overlap))]


def reduce(trace, chips: int = 1, kernels=()) -> Dict[str, Any]:
    """Device metrics of the traced window. ``kernels`` maps each kernel
    whose events are kept to its trace names (a plain sequence: the names
    are the kernels)."""
    t0, t1 = _window(trace)
    devices = _device_lines(trace, chips)
    for lines in devices.values():
        marks = [s for _, s, _ in lines.pop(STOPPED_LINE, ()) if t0 <= s < t1]
        t1 = min([t1] + marks)
    if not isinstance(kernels, dict):
        kernels = {k: (k,) for k in kernels}
    kernel_of = {name: k for k, names in kernels.items() for name in names}
    busy_ns, op_ns = 0, defaultdict(int)
    kernel_events = {k: [] for k in kernels}
    gaps = defaultdict(int)
    names, starts, ends = _host_events(trace, t0, t1)

    def clip(events):
        return [(n, max(s, t0), min(e, t1)) for n, s, e in events
                if e > t0 and s < t1]

    for chip, lines in sorted(devices.items()):
        for n, s, e in clip(lines.get(MODULES_LINE, ())):
            op_ns[module_name(n)] += e - s
        for n, s, e in clip(lines.get(OPS_LINE, ())):
            k = kernel_of.get(op_name(n))
            if k is not None:
                kernel_events[k].append((s, e - s))
                op_ns[f"{k} (kernel)"] += e - s
        busy = _union([(s, e) for events in lines.values()
                       for _, s, e in clip(events)])
        busy_ns += sum(e - s for s, e in busy)
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 - g0 >= GAP_NS:
                gaps[_attribute((g0, g1), names, starts, ends)] += g1 - g0
            elif g1 > g0:
                gaps[SHORT_GAPS] += g1 - g0
    n_chips = max(len(devices), 1)
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n_chips,
        "chips_traced": len(devices),
        "device_ops": sorted(((n, v * 1e-9 / n_chips) for n, v in op_ns.items()),
                             key=lambda kv: -kv[1]),
        "idle_gaps": sorted(((n, v * 1e-9 / n_chips) for n, v in gaps.items()),
                            key=lambda kv: -kv[1]),
        "kernel_events": kernel_events,
        "annotations": [(n, int(s), int(e))
                        for n, s, e in zip(names, starts, ends)
                        if n.startswith("bench.") and s >= t0],
    }
