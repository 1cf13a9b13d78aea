"""The trace reduction on a small trace recorded on the chip."""

import json
import os

import pytest

from bench import trace as tracing

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_fleet_slice.json")


@pytest.fixture(scope="module")
def recorded():
    with open(DATA, encoding="utf-8") as fh:
        return json.load(fh)


def _window(tr):
    for plane in tr["planes"]:
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == "bench.window":
                    return start, start + dur


def _device_events(tr):
    for plane in tr["planes"]:
        if plane["name"] == "/device:TPU:0":
            for line in plane["lines"]:
                for name, start, dur in line["events"]:
                    yield line["name"], name, start, start + dur


def test_busy_and_idle_against_a_sweep_of_every_boundary(recorded):
    t0, t1 = _window(recorded)
    spans = [(max(s, t0), min(e, t1)) for _, _, s, e in _device_events(recorded)
             if e > t0 and s < t1 and e > s]
    cuts = sorted({t0, t1} | {x for iv in spans for x in iv})
    busy = sum(b - a for a, b in zip(cuts, cuts[1:])
               if any(s <= a and b <= e for s, e in spans))
    r = tracing.reduce(recorded, chips=1, kernels=("custom-call",))
    assert r["chips_traced"] == 1
    assert r["window_s"] == pytest.approx((t1 - t0) * 1e-9)
    assert r["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-12)
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-9)
    # the device idles ~11% of this decision (the 78 ms slice)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.1114, abs=1e-4)


def test_program_and_kernel_time(recorded):
    t0, t1 = _window(recorded)
    r = tracing.reduce(recorded, chips=1, kernels=("custom-call",))
    ops = dict(r["device_ops"])
    want = sum(min(e, t1) - max(s, t0) for line, n, s, e in _device_events(recorded)
               if line == "XLA Modules" and n.startswith("jit_optimize_acquisition("))
    assert ops["jit_optimize_acquisition"] == pytest.approx(want * 1e-9)
    kernel = [(n, s, e) for line, n, s, e in _device_events(recorded)
              if line == "XLA Ops" and n.startswith("%custom-call")
              and e > t0 and s < t1 and e > s]
    assert len(r["kernel_events"]["custom-call"]) == len(kernel) > 0
    assert ops["custom-call (kernel)"] == pytest.approx(
        sum(min(e, t1) - max(s, t0) for _, s, e in kernel) * 1e-9)


def test_idle_gaps_named_by_the_host(recorded):
    r = tracing.reduce(recorded, chips=1)
    name, seconds = r["idle_gaps"][0]
    # the longest idle stretch is the service's host work between the
    # device programs of one decision
    assert name == "$service.py:558 suggest_batch"
    assert seconds == pytest.approx(0.007214994)
    assert [a[0] for a in r["annotations"]] == [
        "bench.acq_opt s=10 n=64 d=4 a=1024 r=8"]


def test_kernel_found_by_its_trace_names():
    from bench import spec

    kernels = spec.kernels()
    event = "%acq_score_pallas.2 = f32[10,1,1024]{2,1,0} custom-call("
    trace = {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "t", "events": [["bench.window", 0, 1000]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [[event, 100, 50]]}]}]}
    r = tracing.reduce(trace, chips=1, kernels=kernels)
    assert r["kernel_events"]["acq_score"] == [(100, 50)]


def test_trace_stopped_by_a_full_buffer_ends_the_window(recorded):
    import copy

    t0, t1 = _window(recorded)
    whole = tracing.reduce(recorded, chips=1)
    stop = t0 + (t1 - t0) // 2
    cut = copy.deepcopy(recorded)
    for plane in cut["planes"]:
        if plane["name"] == "/device:TPU:0":
            plane["lines"].append({"name": "XLA TraceMe",
                                   "events": [["stopped", stop, t1 - stop]]})
    r = tracing.reduce(cut, chips=1)
    assert r["window_s"] == pytest.approx((stop - t0) * 1e-9)
    assert r["busy_s"] < whole["busy_s"]
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-9)


def test_op_names():
    assert tracing.op_name("%acq_score.3 = f32[10,1,1024]{2,1,0} custom-call(") == "acq_score"
    assert tracing.op_name("%fusion.12 = f32[8]{0} fusion(%acq_score.3)") == "fusion"
    assert tracing.module_name("jit_optimize_acquisition(9940947245787747984)") == \
        "jit_optimize_acquisition"
