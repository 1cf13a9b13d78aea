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
  objective for all jobs, each its own history), else each its own.

The configuration gives the number of jobs and of workers per job. Each job
keeps ``workers`` trials in flight: a step frees the oldest, then asks for
``k`` more with the other ``workers - 1`` (and any earlier picks) pending.
Every client call is wrapped in a profiler annotation named for it.
"""

from __future__ import annotations

import collections
import time
from typing import Any, Dict, List

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


class Job:
    """One tuning job's client: its history, its in-flight trials and the
    record of every decision it asked for."""

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
                   n_rows=n_rows, pending=pending)
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
