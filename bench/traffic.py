"""The one traffic generator: closed-loop tuning jobs, from a mix's data.

A mix (``bench/traffic/<name>.json``) gives:

* ``history``: completed trials per job at registration (drawn uniformly in
  the encoded cube from the seed; objective values from the seeded
  closed-form objective of ``bench/objective.py``);
* ``k``: configurations per ``suggest_batch`` call;
* ``free``: what becomes of the oldest in-flight trial at each step:
  ``withdraw`` (stopped with no result: the history stays fixed) or
  ``complete`` (its objective is pushed: the history grows by one);
* ``row_cap``: a job whose history reaches it withdraws from then on, so
  the posterior's row bucket never changes inside a window;
* ``warmup_steps``: steps per job run in set-up after the jobs' slots are
  first filled;
* ``shared_objective``: whether every job tunes the same task (one
  objective for all jobs, each its own history), else each its own;
* ``client_processes`` (default 0): 0 drives every job from a thread of the
  harness's process; P > 0 splits the jobs evenly over P client processes
  of their own (``spawn``, on the CPU), as a deployment's clients sit on the
  jobs' own hosts.

The configuration gives the number of jobs and of workers per job. Job j's
client leases replica j mod the number of replicas first (``lease_order``),
as ``chip_smoke.py``'s fleet phase places its jobs. Each job
keeps ``workers`` trials in flight: a step frees the oldest, then asks for
``k`` more with the other ``workers - 1`` (and any earlier picks) pending.
Every decision records the replica that answered it. Every client call is
wrapped in a profiler annotation named for it.
"""

from __future__ import annotations

import collections
import multiprocessing
import os
import threading
import time
from multiprocessing.connection import wait
from typing import Any, Dict, List, Sequence

import jax
import numpy as np

from bench import objective as objectives
from bench.reference import encode, in_bounds


def job_seeds(seed: int, j: int, shared_objective: bool = False):
    """(data seed, engine seed, objective seed) of job ``j``; the engine's
    fits in 31 bits. With ``shared_objective`` every job gets job 0's
    objective seed."""
    data = np.random.SeedSequence([seed, j, 0]).generate_state(2)
    engine = int(np.random.SeedSequence([seed, j, 1]).generate_state(1)[0])
    task = 0 if shared_objective else j
    objective = int(np.random.SeedSequence([seed, task, 2]).generate_state(1)[0])
    return [int(v) for v in data], engine & 0x7FFFFFFF, objective


def answered_by(handle):
    """The address of the replica the job's handle is leased on, or None
    where it holds no connection."""
    conn = getattr(handle, "_conn", None)
    return None if conn is None else tuple(conn.address)


class Job:
    """One tuning job's client: its history, its in-flight trials and the
    record of every decision it asked for. Pickled (from a client process),
    it is that record alone: no handle, no objective."""

    def __init__(self, name, handle, space, mix, workers, data_seed,
                 objective_seed):
        self.name = name
        self.handle = handle
        self.space = space
        self.mix = mix
        self.workers = workers
        self.objective = objectives.make(space, objective_seed)
        self.rng = np.random.default_rng(data_seed)
        self.rows_x: List[Dict[str, Any]] = []
        self.rows_y: List[float] = []
        self.inflight = collections.deque()
        self.decisions: List[Dict[str, Any]] = []
        self.by_ordinal: Dict[int, Dict[str, Any]] = {}  # n-th suggest call
        self._calls = 0
        self._next_key = 0

    def __getstate__(self):
        state = dict(self.__dict__)
        state["handle"] = state["objective"] = None
        return state

    def preload(self, n: int) -> None:
        for config in objectives.sample(self.space, self.rng, n):
            self._push(config)

    def _push(self, config) -> None:
        y = self.objective(config)
        with jax.profiler.TraceAnnotation("client.observe"):
            self.handle.store.push(config, y)
        self.rows_x.append(config)
        self.rows_y.append(y)

    def fill(self) -> None:
        while len(self.inflight) < self.workers:
            self.decide()

    def step(self) -> None:
        """Free the oldest in-flight trial, then decide. A step that fails
        is recorded as a failed decision, and raises."""
        t0 = time.monotonic()
        try:
            key, config = self.inflight.popleft()
            with jax.profiler.TraceAnnotation("client.observe"):
                self.handle.store.clear_pending(key)
            if (self.mix["free"] == "complete"
                    and len(self.rows_x) < self.mix["row_cap"]):
                self._push(config)
            self.decide()
        except Exception as e:
            self.decisions.append(dict(job=self.name, t0=t0,
                                       t1=time.monotonic(), configs=[],
                                       n_rows=len(self.rows_x), pending=[],
                                       error=f"{type(e).__name__}: {e}"))
            raise

    def decide(self) -> None:
        pending = [c for _, c in self.inflight]
        n_rows = len(self.rows_x)
        ordinal, self._calls = self._calls, self._calls + 1
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("client.suggest_batch"):
            configs = self.handle.suggest_batch(self.mix["k"])
        t1 = time.monotonic()
        dec = dict(job=self.name, t0=t0, t1=t1, configs=configs,
                   n_rows=n_rows, pending=pending,
                   replica=answered_by(self.handle))
        self.by_ordinal[ordinal] = dec
        self.decisions.append(dec)
        for config in configs:
            key = f"t{self._next_key}"
            self._next_key += 1
            with jax.profiler.TraceAnnotation("client.observe"):
                self.handle.store.mark_pending(key, config)
            self.inflight.append((key, config))

    def bad_configs(self, since: float) -> int:
        """Configurations returned by decisions that started at ``since`` or
        later that are out of bounds, or equal to a completed, pending or
        earlier-picked configuration of the job at the time."""
        bad = 0
        for dec in self.decisions:
            if dec["t0"] < since or "error" in dec:
                continue
            seen = [encode(self.space, c)
                    for c in self.rows_x[:dec["n_rows"]] + dec["pending"]]
            for config in dec["configs"]:
                if not in_bounds(self.space, config):
                    bad += 1
                    continue
                vec = encode(self.space, config)
                if seen and float(np.min(np.max(np.abs(np.stack(seen) - vec),
                                                 axis=1))) <= 0.0:
                    bad += 1
                seen.append(vec)
        return bad


# ------------------------------------------------------------ the clients


def lease_order(j: int, addresses: Sequence[tuple]) -> List[tuple]:
    """The replica addresses in the order job ``j``'s client leases them:
    replica j mod their number first, then the rest in turn."""
    r = j % len(addresses)
    return list(addresses[r:]) + list(addresses[:r])


def open_jobs(cfg, mix, seed: int, indices: Sequence[int],
              addresses: Sequence[tuple]) -> List[Job]:
    """Register jobs ``indices`` of the configuration, each with its own
    ``RemoteService`` over the addresses in its lease order, and push their
    histories."""
    from bench import spec
    from repro.distributed import RemoteService

    prog_space = spec.search_space(cfg)
    history = mix.get("history", cfg["history"])
    jobs = []
    for j in indices:
        client = RemoteService(lease_order(j, addresses),
                               bo_config=spec.bo_config(cfg),
                               **cfg["client"])
        data_seed, engine_seed, objective_seed = job_seeds(
            seed, j, mix.get("shared_objective", False))
        name = f"job-{j}"
        handle = client.register_job(name, prog_space, seed=engine_seed,
                                     fold_siblings=False)
        job = Job(name, handle, cfg["space"], mix, cfg["workers"], data_seed,
                  objective_seed)
        job.preload(history)
        jobs.append(job)
    return jobs


class Loop:
    """The closed loop of ``jobs``, one thread each: set-up (fill the
    in-flight slots, run ``warmup_steps`` steps), then a barrier, then steps
    until the deadline (a ``time.monotonic()`` value, system-wide on Linux).
    As the cell's clients, these are threads of the harness's process."""

    def __init__(self, jobs: List[Job], mix):
        self.jobs, self.mix = jobs, mix
        self.errors: List[str] = []
        self.deadline = None
        self._barrier = threading.Barrier(len(jobs) + 1)
        self._threads = [threading.Thread(target=self._drive, args=(job,),
                                          daemon=True) for job in jobs]
        for t in self._threads:
            t.start()

    def _drive(self, job: Job) -> None:
        try:
            job.fill()
            for _ in range(self.mix["warmup_steps"]):
                job.step()
        except Exception as e:  # noqa: BLE001 — set-up failed: no result
            self.errors.append(f"{job.name} set-up: {type(e).__name__}: {e}")
            self._barrier.abort()
            return
        try:
            self._barrier.wait()
        except threading.BrokenBarrierError:
            return
        while time.monotonic() < self.deadline:
            try:
                job.step()
            except Exception:  # noqa: BLE001 — counted in job.failed
                return

    def ready(self) -> None:
        """Wait until every job has set up; a set-up failure raises."""
        while self._barrier.n_waiting < len(self.jobs) and not self.errors:
            time.sleep(0.01)
        if self.errors:
            raise RuntimeError("; ".join(self.errors))

    def go(self, deadline: float) -> None:
        self.deadline = deadline
        self._barrier.wait()

    def finish(self):
        """Wait for every job's last step: (the time the last one ended,
        every job's record)."""
        for t in self._threads:
            t.join()
        return time.monotonic(), self.jobs

    def close(self) -> None:
        self._barrier.abort()
        for job in self.jobs:
            job.handle.close()


def client_process(conn, cfg, mix, seed: int, indices: Sequence[int],
                   addresses: Sequence[tuple]) -> None:
    """A client process: set up its jobs and report ``("ready", None)`` or
    ``("error", message)``; take the deadline (None: stop) and drive the
    closed loop to it; send ``("done", (end, jobs' records))``."""
    loop = None
    try:
        try:
            loop = Loop(open_jobs(cfg, mix, seed, indices, addresses), mix)
            loop.ready()
        except Exception as e:  # noqa: BLE001 — reported to the harness
            conn.send(("error", f"client process {os.getpid()}: "
                                f"{type(e).__name__}: {e}"))
            return
        conn.send(("ready", None))
        deadline = conn.recv()
        if deadline is None:
            return
        loop.go(deadline)
        conn.send(("done", loop.finish()))
    finally:
        if loop is not None:
            loop.close()
        conn.close()


class Processes:
    """The clients as ``processes`` client processes (``spawn``, with
    ``JAX_PLATFORMS=cpu``, so that none opens the chip), the jobs split
    evenly over them in order."""

    TIMEOUT = 900.0  # seconds a client process may take to set up or end

    def __init__(self, cfg, mix, seed: int, addresses: Sequence[tuple],
                 processes: int):
        ctx = multiprocessing.get_context("spawn")
        jobs = range(cfg["jobs"])
        shares = [jobs[p * len(jobs) // processes:
                       (p + 1) * len(jobs) // processes]
                  for p in range(processes)]
        self.conns, self.procs = [], []
        prior = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            for share in shares:
                ours, theirs = ctx.Pipe()
                proc = ctx.Process(target=client_process, daemon=True,
                                   args=(theirs, cfg, mix, seed, list(share),
                                         list(addresses)))
                proc.start()
                theirs.close()
                self.conns.append(ours)
                self.procs.append(proc)
        finally:
            if prior is None:
                os.environ.pop("JAX_PLATFORMS", None)
            else:
                os.environ["JAX_PLATFORMS"] = prior

    def _gather(self, expect: str) -> list:
        """Each process's next message, in process order; a process that
        sends anything else, or nothing in time, raises."""
        out = [None] * len(self.conns)
        pending = dict(enumerate(self.conns))
        limit = time.monotonic() + self.TIMEOUT
        while pending:
            ready = wait(list(pending.values()),
                         timeout=max(0.0, limit - time.monotonic()))
            if not ready:
                raise RuntimeError(f"{len(pending)} client processes sent "
                                   f"no {expect!r} in {self.TIMEOUT:.0f} s")
            for p, conn in list(pending.items()):
                if conn not in ready:
                    continue
                try:
                    kind, body = conn.recv()
                except EOFError:
                    raise RuntimeError(f"client process {p} ended without "
                                       f"{expect!r}") from None
                if kind != expect:
                    raise RuntimeError(body)
                out[p] = body
                del pending[p]
        return out

    def ready(self) -> None:
        self._gather("ready")

    def go(self, deadline: float) -> None:
        for conn in self.conns:
            conn.send(deadline)

    def finish(self):
        """(time the last step of any process ended, every job's record)."""
        done = self._gather("done")
        return (max(end for end, _ in done),
                [job for _, jobs in done for job in jobs])

    def close(self) -> None:
        for conn in self.conns:
            try:
                conn.send(None)
            except OSError:
                pass  # the process has ended
        for proc in self.procs:
            proc.join(timeout=30.0)
            if proc.is_alive():
                proc.terminate()
                proc.join()
        for conn in self.conns:
            conn.close()


def clients(cfg, mix, seed: int, addresses: Sequence[tuple]):
    """The cell's clients, as ``mix["client_processes"]`` says."""
    processes = mix.get("client_processes", 0)
    if processes:
        return Processes(cfg, mix, seed, addresses, processes)
    return Loop(open_jobs(cfg, mix, seed, range(cfg["jobs"]), addresses), mix)
