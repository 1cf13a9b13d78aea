"""Several one-chip replicas in one process, their jobs' clients in client
processes of their own: a run of the four-chip cell at a CPU size, on four
virtual CPU devices (in a process of its own, which sets them up): sound,
with each placement fault planted, and with two faults of the engine
planted under every replica. The metrics' arithmetic on synthetic
records."""

import json
import os
import subprocess
import sys
import types

import pytest

from bench import spec, traffic
from bench.metrics import configs_per_s, replica_skew

CELL = "fleet-d4.4chips"
SEED = 2**33 + 41

RUNS = r"""
import json, sys
import jax
import numpy as np
import repro.core.suggest as suggest_mod
from conftest import small
from bench import spec, traffic
from bench.run import run_cell
from repro.distributed import EngineServer


def run(processes, **size):
    cell, cfg, mix = small(sys.argv[1], jobs=8, **size)
    mix["client_processes"] = processes
    held = []
    shutdown = EngineServer.shutdown

    def counting(server):
        held.append(len(server.service._jobs))
        shutdown(server)

    EngineServer.shutdown = counting
    try:
        result = run_cell(cell, cfg, mix, spec.limits(cell["name"]),
                          int(sys.argv[2]), 1.5, True, require_tpu=False,
                          control=True, log=lambda msg: None)
    finally:
        EngineServer.shutdown = shutdown
    result["jobs_per_replica"] = held
    return result


# 150 rows: conditioned like the cell's histories, where float32 fails
print(json.dumps(run(2, history=150)), flush=True)
# every job leases its first address, as RemoteService alone places jobs
order = traffic.lease_order
traffic.lease_order = lambda j, addresses: list(addresses)
print(json.dumps(run(0)), flush=True)
traffic.lease_order = order
# every replica serves from device 0
init = EngineServer.__init__
EngineServer.__init__ = lambda self, *a, device=None, **k: init(
    self, *a, device=jax.devices()[0], **k)
print(json.dumps(run(0)), flush=True)
EngineServer.__init__ = init
# the posterior's alpha is not refreshed after rows are appended
refresh = suggest_mod.refresh_alpha
suggest_mod.refresh_alpha = lambda post, y: post
print(json.dumps(run(2)), flush=True)
suggest_mod.refresh_alpha = refresh
# the candidates move after they were scored
acq = suggest_mod.optimize_acquisition
suggest_mod.optimize_acquisition = lambda *a: (
    (lambda c, v: (np.clip(np.asarray(c) + 0.05, 0.0, 1.0), v))(*acq(*a)))
print(json.dumps(run(2)), flush=True)
"""


@pytest.fixture(scope="module")
def runs():
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [here, str(spec.ROOT), str(spec.ROOT / "src")]))
    proc = subprocess.run([sys.executable, "-c", RUNS, CELL, str(SEED)],
                          cwd=here, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    names = ("sound", "first", "device0", "stale_alpha", "altered")
    lines = proc.stdout.splitlines()[-len(names):]
    return dict(zip(names, map(json.loads, lines)))


def test_replicas_and_client_processes_run_correct_and_control_not(runs):
    result = runs["sound"]
    assert result["correct"], result["checks"]
    limits = spec.limits(CELL)
    assert any(result["control"][k] > limits[k] for k in result["control"]), (
        result["control"], limits)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["count"] == 4
    assert result["jobs_per_replica"] == [2, 2, 2, 2]
    assert result["checks"]["misplaced"] == {"value": 0, "limit": 0}
    assert result["metrics"]["replica_skew"]["value"] >= 0.0
    for name in ("lock_hold_ms", "pool_hit_share", "fleet_decision_ms.p50"):
        assert name in result["metrics"], name


@pytest.mark.parametrize("fault", ["first", "device0"])
def test_misplaced_jobs_are_caught(runs, fault):
    # "first": the 6 jobs placed on replicas 1-3 lease replica 0;
    # "device0": their posteriors sit on device 0, not their replica's
    result = runs[fault]
    assert not result["correct"]
    assert result["checks"]["misplaced"]["value"] == 6


@pytest.mark.parametrize("fault, check", [("stale_alpha", "alpha_gap"),
                                          ("altered", "acq_excess")])
def test_faults_on_every_replica_are_caught(runs, fault, check):
    # planted under all four replicas, with the clients in processes
    result = runs[fault]
    assert not result["correct"]
    assert result["checks"][check]["value"] > result["checks"][check]["limit"]
    assert result["checks"]["misplaced"]["value"] == 0


def test_replica_skew_reads_the_busiest_replica_against_the_mean():
    a, b, c = ("h", 1), ("h", 2), ("h", 3)
    decisions = ([{"replica": a}] * 6 + [{"replica": b}] * 3
                 + [{"replica": c}] * 3 + [{"replica": a, "error": "x"}] * 9)
    run = types.SimpleNamespace(decisions=decisions, replicas=3)
    assert replica_skew.read(run) == pytest.approx(50.0)  # 6 / 4 - 1
    run.replicas = 4  # a replica that answered none lowers the mean
    assert replica_skew.read(run) == pytest.approx(100.0)  # 6 / 3 - 1
    run.decisions = [{"replica": a}] * 5
    run.replicas = 1
    assert replica_skew.read(run) == 0.0
    run.decisions = []
    assert replica_skew.read(run) is None


def test_job_j_leases_replica_j_mod_replicas_first():
    addresses = [("h", 1), ("h", 2), ("h", 3), ("h", 4)]
    assert traffic.lease_order(0, addresses) == addresses
    assert traffic.lease_order(6, addresses) == [("h", 3), ("h", 4),
                                                 ("h", 1), ("h", 2)]
    # one replica: every job on it, as one RemoteService over it would lease
    assert traffic.lease_order(5, addresses[:1]) == addresses[:1]


def test_configs_per_s_counts_the_decisions_returned_by_the_deadline():
    def dec(t1, k=1, **kw):
        return dict(t0=t1 - 0.5, t1=t1, configs=[{}] * k, **kw)

    decisions = [dec(1.0), dec(2.0, k=2), dec(3.9), dec(4.0),
                 dec(2.5, k=0, error="x"),  # failed: returned nothing
                 dec(4.5), dec(7.0)]  # in flight at the deadline
    run = types.SimpleNamespace(decisions=decisions, window=(0.0, 7.0),
                                deadline=4.0)
    assert configs_per_s.read(run) == pytest.approx(5 / 4.0)
    # however long the last decision takes, the rate stays
    decisions[-1] = dec(40.0)
    run.window = (0.0, 40.0)
    assert configs_per_s.read(run) == pytest.approx(5 / 4.0)
    run.deadline = 0.0
    assert configs_per_s.read(run) is None
