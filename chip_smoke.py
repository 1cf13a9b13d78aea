#!/usr/bin/env python3
"""Smoke test: the BO decision engine on a TPU, through its user entry points.

    python3 chip_smoke.py [--seed N]       # one chip: every phase below
    python3 chip_smoke.py --chips 4        # only the replica fleet, 4 chips

One chip, one process; the script fails (exit != 0) at the first phase that
does not hold:

  device   JAX's first device is a TPU. There is no CPU fallback.
  kernels  acq_score (ei, lcb), acq_score_multi (constrained, pareto, rungs,
           cost) and matern52 gram/cross at S = 10 GPHP samples, 1024
           anchors, d = 8 and npad in {512, 2048}. Each lowered program must
           hold a tpu_custom_call (the kernel is compiled, not interpreted),
           and each result must match the kernel's ref.py, computed in f64 on
           this process's CPU device, to f32 accuracy: within 4x the error
           of the same reference run in f32 on the same inputs.
  engine   a BOSuggester with the paper's defaults (BOConfig(): a 300-step
           slice chain giving 10 draws, 1024 Sobol anchors, 8 x 25 refinement
           steps) and backend="pallas", on a mixed 8-hyperparameter space
           with 500 completed trials: three suggest_batch(4) calls must give
           12 in-bounds, distinct configurations.
  service  one EngineServer on a thread of this process: two jobs driven
           through RemoteService must give exactly the suggestion stream of
           an in-process SelectionService with the same seeds.

With --chips 4 only the fleet runs: four EngineServers in this process, each
on its own chip, eight jobs spread across them and one replica stopped
mid-stream; every job's stream must equal a single SelectionService's on
chip 0.

The last line of stdout is {"ok": true, "device": {...}}. JAX's persistent
compilation cache is JAX_COMPILATION_CACHE_DIR when set, else .jax_cache in
the checkout. Times printed on earlier lines are informational.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import functools
import json
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

S_SAMPLES = 10  # GPHP draws: the paper's slice chain keeps 10
NUM_ANCHORS = 1024  # the paper's Sobol anchor count
KERNEL_D = 8
KERNEL_NPADS = (512, 2048)
KERNEL_THREADS = 4  # concurrent kernel compiles and CPU reference runs
HISTORY = 500  # completed trials preloaded into the engine's job
BATCH, CALLS = 4, 3  # suggest_batch(4), three times
SERVICE_STEPS = 3  # decisions per job in the socket phase
FLEET_JOBS, FLEET_STEPS, FLEET_KILL_AFTER = 8, 6, 3
SERVE_TIMEOUT = 1800.0  # a cold decision compiles for minutes on the chip


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds spent in backend compiles, and persistent-cache hits."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1


# ----------------------------------------------------------------- kernels


def assert_compiled(lowered, name: str) -> None:
    check("tpu_custom_call" in lowered.as_text(),
          f"{name}: lowered program has no tpu_custom_call")


def _compile_on(dev, fn, args):
    """jit ``fn`` for ``dev``: the lowered program (checked for the kernel),
    the executable and its arguments on the device."""
    import jax

    args = jax.device_put(args, dev)
    lowered = jax.jit(fn).lower(*args)
    return lowered, lowered.compile(), args


F32_FACTOR = 4.0  # the kernel may err 4x more than the f32 reference


def _refs(cpu, fn, *args):
    """``fn`` on the CPU device in f64, and on the same inputs cast to f32.

    The f32 run's error is what IEEE f32 arithmetic of the reference's
    algorithm gets wrong with this conditioning (npad-term contractions,
    |alpha| in the tens): the yardstick for a kernel that computes in f32."""
    import jax
    import jax.numpy as jnp

    def f32(tree):
        return jax.tree.map(
            lambda a: a.astype(jnp.float32) if a.dtype == jnp.float64 else a,
            tree)

    with jax.default_device(cpu):
        return fn(*args), fn(*f32(args))


def _compare(name, got, refs):
    import numpy as np

    ref, ref32 = (np.asarray(r, np.float64) for r in refs)
    got = np.asarray(got, np.float64)
    check(got.shape == ref.shape, f"{name}: shape {got.shape} != {ref.shape}")
    check(bool(np.all(np.isfinite(got))), f"{name}: non-finite output")
    err = float(np.max(np.abs(got - ref)))
    err32 = float(np.max(np.abs(ref32 - ref)))
    tol = F32_FACTOR * err32 + 1e-7 * float(np.max(np.abs(ref)))
    print(f"  {name}: tpu_custom_call ok, max|kernel - ref| = {err:.3e}, "
          f"f32 reference {err32:.3e} (tol {tol:.3e})")
    check(err <= tol, f"{name}: max error {err:.3e} > {tol:.3e}")


def _kernel_posterior(rng, npad, cpu):
    """S-sample posterior over npad rows (the last 12 masked), f64 on CPU,
    with the cached inverse factor the pallas engine threads through."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.gp import gp as G
    from repro.core.gp import params as P

    d, n_live = KERNEL_D, npad - 12
    x = np.zeros((npad, d))
    y = np.zeros(npad)
    x[:n_live] = rng.random((n_live, d))
    y[:n_live] = rng.standard_normal(n_live)
    mask = np.arange(npad) < n_live
    base = np.asarray(P.default_params(d).pack())
    packed = base + 0.1 * rng.standard_normal((S_SAMPLES, 3 * d + 2))
    packed[:, d + 1] = np.log(0.3)  # observation noise sd
    with jax.default_device(cpu):
        post = G.fit_posterior_batch(
            jnp.asarray(x), jnp.asarray(y),
            P.GPHyperParams.unpack(jnp.asarray(packed), d),
            jnp.asarray(mask), with_inverse=True,
        )
        y_best = jnp.asarray(0.0)  # incumbent at the mean: EI is O(σ)
    return post, y_best


def _heads(rng, post, mode, cpu):
    """A MultiMetricHead for ``mode`` whose extra-head alphas solve the
    shared factor against random targets."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.gp.per_resource import rung_head_weights
    from repro.core.optimize_acq import MultiMetricHead

    num = {"constrained": 3, "pareto": 3, "rungs": 4, "cost": 2}[mode]
    n = post.x_train.shape[0]
    targets = rng.standard_normal((n, num - 1)) * np.asarray(post.mask)[:, None]
    with jax.default_device(cpu):
        linv = post.chol_inv
        extra = jnp.einsum("sji,sjk->sik", linv, linv @ jnp.asarray(targets))
        alphas = jnp.concatenate(
            [post.alpha[:, None, :], jnp.swapaxes(extra, 1, 2)], axis=1
        )
        w = rng.random((16, 2)) + 1e-3
        spec = {
            "constrained": dict(t_std=[0.4, -0.2], weights=np.zeros((0, 1)),
                                y_best_w=np.zeros(0)),
            "pareto": dict(t_std=[0.4], weights=w / w.sum(1, keepdims=True),
                           y_best_w=rng.standard_normal(16)),
            "rungs": dict(t_std=np.zeros(0),
                          weights=rung_head_weights([1, 3, 9], 3),
                          y_best_w=rng.standard_normal(4)),
            "cost": dict(t_std=np.zeros(0), weights=[[0.7]],
                         y_best_w=np.zeros(1)),
        }[mode]
        return MultiMetricHead(
            alphas=alphas,
            t_std=jnp.asarray(spec["t_std"], jnp.float64),
            y_best=jnp.asarray(-0.6),
            has_feasible=jnp.asarray(True),
            weights=jnp.asarray(spec["weights"], jnp.float64),
            y_best_w=jnp.asarray(spec["y_best_w"], jnp.float64),
        )


def _kernel_cases(seed, cpu):
    """(name, kernel fn, args, reference fn) for every kernel and npad."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.gp.params import GPHyperParams
    from repro.kernels.acq_score.ops import acq_score, acq_score_multi
    from repro.kernels.acq_score.ref import acq_score_multi_ref, acq_score_ref
    from repro.kernels.matern52.ops import matern52_cross, matern52_gram
    from repro.kernels.matern52.ref import matern52_cross_ref, matern52_gram_ref

    rng = np.random.default_rng(seed)
    for npad in KERNEL_NPADS:
        post, y_best = _kernel_posterior(rng, npad, cpu)
        with jax.default_device(cpu):
            x = jnp.asarray(rng.random((NUM_ANCHORS, KERNEL_D)))
        for acq in ("ei", "lcb"):
            yield (
                f"acq_score[{acq}, npad={npad}]",
                functools.partial(acq_score, acq=acq, backend="pallas"),
                (post, x, y_best),
                functools.partial(acq_score_ref, acq=acq),
            )
        for mode in ("constrained", "pareto", "rungs", "cost"):
            def ref(p, h, x, mode=mode):
                return acq_score_multi_ref(
                    p, h.alphas, x, mode=mode, t_std=h.t_std, y_best=h.y_best,
                    has_feasible=True, weights=h.weights, y_best_w=h.y_best_w,
                )

            yield (
                f"acq_score_multi[{mode}, npad={npad}]",
                functools.partial(acq_score_multi, mode=mode, backend="pallas"),
                (post, _heads(rng, post, mode, cpu), x),
                ref,
            )

        d = KERNEL_D
        with jax.default_device(cpu):
            params = GPHyperParams(
                log_lengthscale=jnp.asarray(rng.normal(0.0, 0.5, d)),
                log_amplitude=jnp.asarray(0.3),
                log_noise=jnp.asarray(-3.0),
                log_warp_a=jnp.asarray(rng.normal(0.0, 0.3, d)),
                log_warp_b=jnp.asarray(rng.normal(0.0, 0.3, d)),
            )
            xs = jnp.asarray(rng.random((npad, d)))
            x_new = jnp.asarray(rng.random(d))
        yield (f"matern52_gram[npad={npad}]", matern52_gram, (xs, xs, params),
               matern52_gram_ref)
        yield (f"matern52_cross[npad={npad}]", matern52_cross,
               (x_new, xs, params), matern52_cross_ref)


def phase_kernels(seed, dev, cpu):
    """Compile every case for the chip and compute its references on the
    CPU, concurrently (compiles and CPU references release the GIL); then
    run and compare in order."""
    cases = list(_kernel_cases(seed, cpu))
    with concurrent.futures.ThreadPoolExecutor(KERNEL_THREADS) as pool:
        built = [pool.submit(_compile_on, dev, fn, args)
                 for _, fn, args, _ in cases]
        refs = [pool.submit(_refs, cpu, ref, *args)
                for _, _, args, ref in cases]
        for (name, _, _, _), b, r in zip(cases, built, refs):
            lowered, exe, args = b.result()
            assert_compiled(lowered, name)
            _compare(name, exe(*args), r.result())


# ------------------------------------------------------------------ engine


def mixed_space():
    from repro.core import Categorical, Continuous, Integer, SearchSpace

    return SearchSpace([
        Continuous("learning_rate", 1e-5, 1e-1, scaling="log"),
        Continuous("weight_decay", 1e-6, 1e-2, scaling="log"),
        Continuous("momentum", 0.5, 0.99),
        Continuous("dropout", 0.0, 0.5),
        Integer("batch_size", 16, 512, scaling="log"),
        Integer("num_layers", 1, 8),
        Categorical("optimizer", ["sgd", "adam", "adamw"]),
        Categorical("activation", ["relu", "gelu"]),
    ])


def closed_form_objective(space, seed):
    """A smooth bowl with ripples over the encoded cube; optimum drawn from
    ``seed``."""
    import numpy as np

    rng = np.random.default_rng(seed)
    center = rng.uniform(0.2, 0.8, space.encoded_dim)
    weight = rng.uniform(0.5, 2.0, space.encoded_dim)

    def objective(config):
        u = space.encode(config)
        return float(np.sum(weight * (u - center) ** 2)
                     + 0.1 * np.sum(np.sin(7.0 * u)))

    return objective


def history(space, objective, seed, n):
    import numpy as np

    return [(c, objective(c))
            for c in space.sample(np.random.default_rng(seed + 1), n)]


def phase_engine(seed, clock, dev):
    import numpy as np

    from repro.core import BOConfig, BOSuggester, ObservationStore
    from repro.core import suggest as engine
    from repro.core.warm_start import transferable

    space = mixed_space()
    objective = closed_form_objective(space, seed)
    store = ObservationStore(space)
    for config, y in history(space, objective, seed, HISTORY):
        store.push(config, y)
    suggester = BOSuggester(space, BOConfig(backend="pallas"), seed=seed,
                            store=store)
    # record the engine's calls of its acquisition program, to lower the
    # last one again below and look for the fused kernel in it
    acq_opt, acq_calls = engine.optimize_acquisition, []

    def recorded(*args, **kwargs):
        acq_calls.append((args, kwargs))
        return acq_opt(*args, **kwargs)

    out, times = [], []
    compile_before = clock.seconds
    engine.optimize_acquisition = recorded
    try:
        for call in range(CALLS):
            t0 = time.perf_counter()
            batch = suggester.suggest_batch(BATCH)
            times.append(time.perf_counter() - t0)
            for i, config in enumerate(batch):
                store.mark_pending(f"call{call}-{i}", config)
            out.extend(batch)
    finally:
        engine.optimize_acquisition = acq_opt
    print(f"  cold decision: {times[0]:.1f} s, of which "
          f"{clock.seconds - compile_before:.1f} s backend compile")
    print("  warm decisions: "
          + ", ".join(f"{t * 1e3:.0f} ms" for t in times[1:]))
    placed = set(suggester.cache.post.chol.devices())
    check(placed == {dev}, f"engine: the posterior is on {placed}, not {dev}")
    check(bool(acq_calls), "engine: optimize_acquisition was never called")
    args, kwargs = acq_calls[-1]
    assert_compiled(acq_opt.lower(*args, **kwargs), "engine: optimize_acquisition")
    print(f"  posterior on {dev}; optimize_acquisition holds tpu_custom_call")
    check(len(out) == BATCH * CALLS, f"engine: {len(out)} configs")
    check(all(transferable(space, c) for c in out),
          "engine: a suggestion is out of bounds")
    seen = np.stack([space.encode(c) for c, _ in history(space, objective,
                                                           seed, HISTORY)])
    vecs = np.stack([space.encode(c) for c in out])
    for i, v in enumerate(vecs):
        others = np.concatenate([seen, vecs[:i]])
        check(float(np.min(np.max(np.abs(others - v), axis=1))) > 0.0,
              f"engine: suggestion {i} duplicates an earlier configuration")
    print(f"  {len(out)} suggestions, in bounds and distinct")


# ----------------------------------------------------------------- service


def _service_config():
    from repro.core import BOConfig, ServiceConfig

    return ServiceConfig(share_gphp=False, sibling_warm_start=False,
                         default_bo_config=BOConfig(backend="pallas"))


def drive_jobs(handles, objective, steps, push):
    """Interleaved decisions over ``handles``; returns each job's stream.
    ``push`` completes each suggestion (the store grows); otherwise the
    suggestions stay pending."""
    streams = {name: [] for name in handles}
    for step in range(steps):
        for name, handle in handles.items():
            batch = handle.suggest_batch(1 if push else 2)
            streams[name].extend(batch)
            for i, config in enumerate(batch):
                key = f"{step}-{i}"
                handle.store.mark_pending(key, config)
                if push:
                    handle.store.clear_pending(key)
                    handle.store.push(config, objective(config))
    return streams


def _register(service, names, space, cfg, seed, rows):
    handles = {}
    for j, name in enumerate(names):
        handles[name] = service.register_job(name, space, bo_config=cfg,
                                             seed=seed + j)
        for config, y in rows:
            handles[name].store.push(config, y)
    return handles


def phase_service(seed):
    from repro.core import SelectionService
    from repro.distributed import EngineServer, RemoteService

    space = mixed_space()
    objective = closed_form_objective(space, seed)
    rows = history(space, objective, seed, HISTORY)
    svc_cfg = _service_config()
    cfg = svc_cfg.default_bo_config
    names = ["job-a", "job-b"]

    t0 = time.perf_counter()
    local = _register(SelectionService(svc_cfg), names, space, cfg, seed, rows)
    want = drive_jobs(local, objective, SERVICE_STEPS, push=False)
    t1 = time.perf_counter()
    server = EngineServer(service_config=svc_cfg,
                          lease_ttl=SERVE_TIMEOUT).start()
    try:
        remote = RemoteService([server.address], call_timeout=SERVE_TIMEOUT)
        handles = _register(remote, names, space, cfg, seed, rows)
        got = drive_jobs(handles, objective, SERVICE_STEPS, push=False)
        for h in handles.values():
            h.close()
    finally:
        server.shutdown()
    t2 = time.perf_counter()
    print(f"  in-process: {t1 - t0:.1f} s; over the socket: {t2 - t1:.1f} s")
    for name in names:
        check(got[name] == want[name],
              f"service: {name}'s socket stream differs from in-process")
    print(f"  {len(names)} jobs x {2 * SERVICE_STEPS} suggestions: socket "
          "stream == in-process stream")


# ------------------------------------------------------------------- fleet


def fleet_space():
    from repro.core import Continuous, SearchSpace

    return SearchSpace([
        Continuous("learning_rate", 1e-5, 1e-1, scaling="log"),
        Continuous("weight_decay", 1e-6, 1e-2, scaling="log"),
        Continuous("momentum", 0.5, 0.99),
    ])


def _fleet_config():
    from repro.core import BOConfig, ServiceConfig
    from repro.core.gp.slice_sampler import FAST_CONFIG

    return ServiceConfig(
        share_gphp=False, sibling_warm_start=False,
        default_bo_config=BOConfig(num_init=3, slice_config=FAST_CONFIG,
                                   backend="pallas"),
    )


def phase_fleet(seed, devices):
    """Four replicas in this process, one per chip; eight jobs; one replica
    stopped mid-stream. The reference runs concurrently on chip 0, so every
    chip compiles at the same time."""
    import jax

    from repro.core import SelectionService
    from repro.distributed import EngineServer, RemoteService

    space = fleet_space()
    objective = closed_form_objective(space, seed)
    svc_cfg = _fleet_config()
    cfg = svc_cfg.default_bo_config
    names = [f"fleet-{j}" for j in range(FLEET_JOBS)]
    servers = [EngineServer(service_config=svc_cfg, lease_ttl=SERVE_TIMEOUT,
                            device=dev).start() for dev in devices]
    addresses = [s.address for s in servers]
    victim = 1
    want, got, errors = {}, {}, []

    def reference():
        with jax.default_device(devices[0]):
            svc = SelectionService(svc_cfg)
            handles = _register(svc, names, space, cfg, seed, [])
            want.update(drive_jobs(handles, objective, FLEET_STEPS, push=True))

    def stop_victim():
        servers[victim].shutdown()
        print(f"  stopped replica {victim} ({devices[victim]}) after "
              f"{FLEET_KILL_AFTER} decisions per job")

    barrier = threading.Barrier(FLEET_JOBS, action=stop_victim)

    def job(j):
        # job j leases replica j % 4 first and fails over to the next one
        order = addresses[j % 4:] + addresses[:j % 4]
        remote = RemoteService(order, snapshot_every=2,
                               call_timeout=SERVE_TIMEOUT)
        handle = _register(remote, [names[j]], space, cfg, seed + j, [])
        stream = drive_jobs(handle, objective, FLEET_KILL_AFTER,
                            push=True)[names[j]]
        barrier.wait()
        more = _resume(handle[names[j]], objective, FLEET_KILL_AFTER,
                       FLEET_STEPS)
        got[names[j]] = stream + more
        handle[names[j]].close()

    def guarded(fn, *a):
        try:
            fn(*a)
        except BaseException as e:  # noqa: BLE001 — reported below
            errors.append(f"{fn.__name__}{a}: {type(e).__name__}: {e}")
            barrier.abort()

    t0 = time.perf_counter()
    threads = [threading.Thread(target=guarded, args=(reference,))]
    threads += [threading.Thread(target=guarded, args=(job, j))
                for j in range(FLEET_JOBS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    print(f"  fleet + reference: {time.perf_counter() - t0:.1f} s")
    try:
        check(not errors, "fleet: " + "; ".join(errors))
        for r, server in enumerate(servers):
            for name, handle in server.service._jobs.items():
                post = handle.suggester.cache.post
                if post is None or r == victim:
                    continue
                placed = set(post.chol.devices())
                check(placed == {devices[r]},
                      f"fleet: {name} on replica {r} holds arrays on {placed}")
            print(f"  replica {r}: jobs {sorted(server.service._jobs)} "
                  f"on {devices[r]}")
        for name in names:
            check(got[name] == want[name],
                  f"fleet: {name}'s stream differs from the chip-0 reference")
        print(f"  {FLEET_JOBS} jobs x {FLEET_STEPS} suggestions across "
              f"{len(devices)} chips == single-service stream on chip 0 "
              "(failover exact)")
    finally:
        for r, server in enumerate(servers):
            if r != victim:
                server.shutdown()


def _resume(handle, objective, start, stop):
    out = []
    for step in range(start, stop):
        config = handle.suggest_batch(1)[0]
        out.append(config)
        key = f"{step}-0"
        handle.store.mark_pending(key, config)
        handle.store.clear_pending(key)
        handle.store.push(config, objective(config))
    return out


# -------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro next to {Path(__file__).name}; run "
              "it from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_persistent_cache

    cache_dir = enable_persistent_cache(ROOT)
    import jax

    jax.config.update("jax_enable_x64", True)  # as repro.core sets it
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}; compile cache: {cache_dir}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is on platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices; "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    clock = CompileClock()
    t_start = time.perf_counter()
    if args.chips == 4:
        phases = [("fleet", lambda: phase_fleet(args.seed, devices[:4]))]
        device["count"] = 4
    else:
        cpu = jax.devices("cpu")[0]
        phases = [
            ("kernels", lambda: phase_kernels(args.seed, dev, cpu)),
            ("engine", lambda: phase_engine(args.seed, clock, dev)),
            ("service", lambda: phase_service(args.seed)),
        ]
    for name, run in phases:
        t0, c0 = time.perf_counter(), clock.seconds
        print(f"[{name}]", flush=True)
        try:
            run()
        except SmokeFailure as e:
            print(f"chip_smoke: phase {name} failed: {e}", file=sys.stderr)
            return 1
        print(f"[{name}] passed in {time.perf_counter() - t0:.1f} s "
              f"({clock.seconds - c0:.1f} s backend compile)", flush=True)
    print(f"total {time.perf_counter() - t_start:.1f} s, backend compile "
          f"{clock.seconds:.1f} s, persistent-cache hits {clock.cache_hits}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
