"""The server's lock read from the engine's spans: ``server.lock_wait``
under ``rpc.suggest_batch`` beside ``service.suggest_batch``."""

import types

import pytest

from bench import spec
from bench.metrics import lock_hold_ms, lock_queue_ms, lock_wait_ms, wire_ms
from bench.metrics._spans import decision_spans
from bench.run import run_cell

FLEET = "fleet-d4.16jobs-same-space"
SEED = 2**33 + 29


def _span(span_id, name, parent, t0, t1, **attrs):
    return {"kind": "span", "name": name, "span_id": span_id,
            "parent_id": parent, "t0": t0, "t1": t1, "dur": t1 - t0,
            "attrs": attrs}


def _decision(base, wait, hold, job):
    """One decision's client call and server spans: the call opens at
    ``base``, the rpc 1 ms later, the lock is taken after ``wait`` and
    held for ``hold``."""
    rpc, lock, svc = base * 10 + 1, base * 10 + 2, base * 10 + 3
    t = base + 0.001
    spans = [_span(rpc, "rpc.suggest_batch", None, t, t + wait + hold),
             _span(lock, "server.lock_wait", rpc, t, t + wait,
                   verb="suggest_batch"),
             _span(svc, "service.suggest_batch", rpc, t + wait,
                   t + wait + hold - 0.0005, job=job, k=1)]
    dec = {"job": job, "t0": base, "t1": t + wait + hold + 0.002,
           "configs": [{}]}
    return dec, spans


def _run(with_lock_wait=True):
    decisions, spans = [], []
    for i, (wait, hold) in enumerate([(0.5, 0.08), (0.7, 0.09), (0.6, 0.1)]):
        dec, ss = _decision(10.0 * (i + 1), wait, hold, f"job-{i}")
        decisions.append(dec)
        spans += [s for s in ss
                  if with_lock_wait or s["name"] != "server.lock_wait"]
    return types.SimpleNamespace(decisions=decisions, spans=spans,
                                 counters={}, trace=None)


def test_decision_spans_pair_every_decision_beside_the_lock_wait():
    run = _run()
    pairs = decision_spans(run)
    assert len(pairs) == len(run.decisions) == 3
    assert all(rpc["name"] == "rpc.suggest_batch"
               and s["name"] == "service.suggest_batch" for _, rpc, s in pairs)
    # the readers that pair through them still read
    assert lock_wait_ms.read(run) == pytest.approx(600.5)
    assert wire_ms.read(run) == pytest.approx(3.0)


def test_lock_queue_and_hold():
    run = _run()
    assert lock_queue_ms.read(run) == pytest.approx(600.0)
    assert lock_hold_ms.read(run) == pytest.approx(90.0)
    # the wait for the lock is part of what lock_wait_ms reads
    assert lock_queue_ms.read(run) <= lock_wait_ms.read(run)


def test_a_program_without_the_span_reads_nothing():
    run = _run(with_lock_wait=False)
    assert lock_queue_ms.read(run) is None
    assert lock_hold_ms.read(run) is None
    assert len(decision_spans(run)) == 3


def test_traced_fleet_run_reports_the_lock(small_cell, monkeypatch):
    """A traced run of the fleet at a CPU size: every decision pairs with
    its spans, and the lock's metrics are in the result."""
    seen = {}
    reader = spec.reader

    def spying(name):
        read = reader(name)

        def wrapped(run):
            seen["run"] = run
            return read(run)
        return wrapped

    monkeypatch.setattr(spec, "reader", spying)
    cell, cfg, mix = small_cell(FLEET, jobs=3)
    result = run_cell(cell, cfg, mix, spec.limits(FLEET), SEED, 1.5, True,
                      require_tpu=False, log=lambda msg: None)
    run = seen["run"]
    served = [d for d in run.decisions if "error" not in d]
    assert served and len(decision_spans(run)) == len(served)
    metrics = result["metrics"]
    for name in ("wire_ms", "lock_wait_ms", "lock_queue_ms", "lock_hold_ms"):
        assert name in metrics, name
    assert metrics["lock_queue_ms"]["value"] <= metrics["lock_wait_ms"]["value"]
