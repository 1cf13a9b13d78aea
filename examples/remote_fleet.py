"""Remote fleet demo: tuning jobs leasing engine replicas over sockets.

    PYTHONPATH=src python examples/remote_fleet.py                # self-hosted
    PYTHONPATH=src python examples/remote_fleet.py --ports 7341,7342

The paper's AMT is a managed service: tuning jobs talk to a fleet of
decision-engine workers behind an API, not to an in-process object (§3,
Fig. 1). This demo is that deployment shape in miniature:

  * two ``EngineServer`` replicas, each hosting a ``SelectionService``
    behind the versioned wire protocol (``repro.core.rpc``);
  * three tuning jobs driving them through ``RemoteService`` — the same
    ``Tuner(service=...)`` API as in-process service mode, but every
    decision, observation, and checkpoint crosses a socket;
  * a mid-run replica **kill**: job 2's replica dies between trials; the
    client re-adopts the job onto the surviving replica from its last
    published engine snapshot and replays the requests since — the
    suggestion stream continues bit-exactly and no trial retry budget is
    consumed (replica death is infrastructure failure, not trial failure).

With ``--ports`` the demo instead connects to replicas you started
yourself (``python -m repro.distributed.engine_server --port 7341``) and
skips the kill (it won't shoot processes it doesn't own). On a host with
accelerators start one such process per host, not per replica: a chip
belongs to the one process that touched JAX first, so the replicas of a
host share one process, each ``EngineServer(device=...)`` on its own chip —
which is how the self-hosted demo starts its two replicas.
"""

import argparse
import math

import jax
import numpy as np

from repro.core import BOConfig, Continuous, SearchSpace, Tuner, TuningJobConfig
from repro.core.scheduler import SimBackend
from repro.core.service import ServiceConfig
from repro.distributed import EngineServer, RemoteService


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ports", default=None,
                    help="comma-separated ports of already-running replicas "
                         "on localhost (default: spawn two in-process)")
    args = ap.parse_args()

    space = SearchSpace([
        Continuous("learning_rate", 1e-5, 1.0, scaling="log"),
        Continuous("weight_decay", 1e-6, 1e-1, scaling="log"),
    ])

    def objective(cfg):
        floor = (
            (math.log10(cfg["learning_rate"]) + 2.5) ** 2
            + 0.3 * (math.log10(cfg["weight_decay"]) + 4.0) ** 2
        )
        return floor + 2.0 * np.exp(-0.4 * np.arange(1, 11)), 1.0

    engine_cfg = ServiceConfig(
        default_bo_config=BOConfig(num_init=3, refit_every=5).fast(),
    )

    servers = []
    if args.ports:
        addresses = [("127.0.0.1", int(p)) for p in args.ports.split(",")]
    else:
        devices = jax.devices()  # one replica per chip, in this process
        servers = [EngineServer(service_config=engine_cfg,
                                device=devices[i % len(devices)]).start()
                   for i in range(2)]
        addresses = [s.address for s in servers]
    print(f"replica fleet: {addresses}")

    service = RemoteService(addresses, snapshot_every=6)
    results = []
    for i in range(3):
        kill = bool(servers) and i == 2
        killed = []

        def chaos(tuner, trial):
            # replica crash mid-job: the next request hits a dead socket,
            # the handle re-adopts on the survivor from its last snapshot.
            done = sum(1 for t in tuner.trials.values() if t.is_terminal)
            if kill and done == 4 and not killed:
                victim = servers.pop(0)
                victim.shutdown()
                killed.append(victim)
                print("  !! killed a replica mid-job — failing over")

        tuner = Tuner(
            space, objective, None,  # suggester is replica-created
            SimBackend(startup_cost=2.0),
            TuningJobConfig(max_trials=10, max_parallel=2,
                            job_name=f"remote-job-{i}", seed=i),
            service=service,
            callbacks=[chaos],
        )
        res = tuner.run()
        results.append(res)
        print(f"remote-job-{i}: best={res.best_objective:.4f} "
              f"({res.num_failed_attempts} failed attempts)")

    assert all(r.num_failed_attempts == 0 for r in results), \
        "replica death must not consume trial retry budget"
    print(f"best objectives: {[round(r.best_objective, 4) for r in results]}")
    for s in servers:
        s.shutdown()


if __name__ == "__main__":
    main()
