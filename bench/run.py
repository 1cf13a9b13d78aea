#!/usr/bin/env python3
"""Run one cell of the decision-service benchmark once, on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix (see
``bench/spec.py``). One process holds the cell's chips and runs the served
path from the client's side: ``RemoteService`` → socket → ``EngineServer``
(a thread of this process, one replica per chip of the cell, job j leased
on replica j mod their number) → ``SelectionService`` → ``BOSuggester`` →
``optimize_acquisition`` with the fused ``acq_score`` Pallas kernel. The
jobs' clients are threads of this process, or client processes of their own
on the CPU (the mix's ``client_processes``, ``bench/traffic.py``).

Set-up (``setup_s``, from the start of this script): the persistent
compilation cache at ``<checkout>/.jax_cache``; the jobs registered and
their histories pushed over the socket; every job's in-flight slots
filled and ``warmup_steps`` steps run, which compiles or loads every
program the window uses; a check that ``optimize_acquisition`` as lowered
for the chip holds ``tpu_custom_call``. The window: the closed loop for
``--seconds``; compilations inside it are counted on stderr. With
``--trace 1`` the engine's telemetry is on for the window and the profiler
traces its first ``trace_seconds``; the result then carries the cell's
per-layer metrics, else its end-to-end ones.

After the window, a seeded sample of the decision slots (and the last one)
is compared with the float64 reference of ``bench/reference.py``, and every
returned configuration is checked: in bounds and distinct from the job's
history and pending trials. With several replicas, every job must also be
on the replica it was placed on (``misplaced``). The numbers compared and their
limits (``bench/limits/<cell>.json``) are printed last on stderr, and last
in the result line, the last line of stdout. No TPU, or fewer chips than
the cell asks for: exit 1 and no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TRACE_CAPACITY = 1 << 20  # telemetry events kept per run


class NoDevice(Exception):
    pass


# --------------------------------------------------------------- recorder


class Recorder:
    """Wraps the engine's ``optimize_acquisition`` and the service's
    ``suggest_batch`` to see what the timed path computes.

    Each acquisition call is annotated in the profiler trace with its
    shapes, for the kernels' work counts. Inside the window it keeps a
    seeded reservoir sample of the calls and the last one: which request
    of which job it served (the job's count of ``suggest_batch`` calls, and
    the slot), the posterior it used, and the candidates and acquisition
    values it returned (device arrays, read back after the window)."""

    def __init__(self):
        import repro.core.suggest as suggest_mod
        from repro.core.service import SelectionService
        from repro.core.suggest import BOSuggester

        self._tls = threading.local()
        self._lock = threading.Lock()
        self.active = False
        self.last_setup_call = None
        self._rng = random.Random(0)
        self._size = 0
        self.sample, self.last, self.seen = [], None, 0
        self._ordinal = {}  # job -> suggest_batch calls the service served
        self._fits = []  # (job, rows, packed draws) of every GPHP fit
        self.acq = suggest_mod.optimize_acquisition
        service_suggest = SelectionService.suggest_batch
        fit_gphps = BOSuggester._fit_gphps
        recorder = self

        def suggest_batch(service, name, k):
            with recorder._lock:
                ordinal = recorder._ordinal.get(name, 0)
                recorder._ordinal[name] = ordinal + 1
            recorder._tls.job, recorder._tls.ordinal = name, ordinal
            recorder._tls.slot = 0
            try:
                return service_suggest(service, name, k)
            finally:
                recorder._tls.job = None

        def optimize_acquisition(post, anchors, y_best, pending, pending_mask,
                                 key, cfg):
            s, n = post.chol.shape[0], post.chol.shape[-1]
            label = (f"bench.acq_opt s={s} n={n} d={anchors.shape[1]} "
                     f"a={cfg.num_anchors} r={cfg.num_refine}")
            import jax

            with jax.profiler.TraceAnnotation(label):
                out = recorder.acq(post, anchors, y_best, pending,
                                   pending_mask, key, cfg)
            recorder._record(
                (post, anchors, y_best, pending, pending_mask, key, cfg), out)
            return out

        def _fit_gphps(suggester, xj, yj, mj, chain_slot=None):
            out = fit_gphps(suggester, xj, yj, mj, chain_slot)
            with recorder._lock:
                recorder._fits.append(
                    (getattr(recorder._tls, "job", None), mj, out))
            return out

        self._restore = [(suggest_mod, "optimize_acquisition", self.acq),
                         (SelectionService, "suggest_batch", service_suggest),
                         (BOSuggester, "_fit_gphps", fit_gphps)]
        suggest_mod.optimize_acquisition = optimize_acquisition
        SelectionService.suggest_batch = suggest_batch
        BOSuggester._fit_gphps = _fit_gphps

    def uninstall(self):
        for owner, attr, value in self._restore:
            setattr(owner, attr, value)

    def start(self, seed: int, size: int) -> None:
        with self._lock:
            self._rng = random.Random(seed)
            self._size = size
            self.sample, self.last, self.seen = [], None, 0
            self.active = True

    def stop(self) -> None:
        with self._lock:
            self.active = False

    def _record(self, args, out) -> None:
        # which decision of which job, and which of its k slots
        slot = getattr(self._tls, "slot", 0)
        self._tls.slot = slot + 1
        entry = ((getattr(self._tls, "job", None),
                  getattr(self._tls, "ordinal", None), slot), args, out)
        with self._lock:
            if not self.active:
                self.last_setup_call = args
                return
            self.seen += 1
            self.last = entry
            if len(self.sample) < self._size:
                self.sample.append(entry)
            else:
                i = self._rng.randrange(self.seen)
                if i < self._size:
                    self.sample[i] = entry

    def calls(self):
        """The sampled calls and the last, each once, read back to host.
        Each names the GPHP fit whose draws it used (``fit``: the fitting
        job and its row count), and how far its draws lie from that fit's
        (``draw_source``: the largest difference; none fit, infinity)."""
        import numpy as np

        fits = [(job, int(np.sum(np.asarray(mask))), np.asarray(out))
                for job, mask, out in self._fits]
        entries = list(self.sample)
        if self.last is not None and all(e is not self.last for e in entries):
            entries.append(self.last)
        out = []
        for (job, ordinal, slot), (post, *_, cfg), (cands, vals) in entries:
            n = int(np.sum(np.asarray(post.mask)))
            params = {k: np.asarray(v) for k, v in post.params._asdict().items()}
            packed = np.concatenate(
                [params["log_lengthscale"], params["log_amplitude"][:, None],
                 params["log_noise"][:, None], params["log_warp_a"],
                 params["log_warp_b"]], axis=1)
            source, dist = None, np.inf
            for fit_job, fit_n, draws in fits:
                if draws.shape == packed.shape:
                    gap = float(np.max(np.abs(draws - packed)))
                    if gap < dist:
                        source, dist = (fit_job, fit_n), gap
            out.append(dict(
                job=job, ordinal=ordinal, slot=slot, n=n, fit=source,
                draw_source=dist, params=params,
                chol=np.asarray(post.chol)[:, :n, :n],
                chol_inv=np.asarray(post.chol_inv)[:, :n, :n],
                alpha=np.asarray(post.alpha)[:, :n],
                cands=np.asarray(cands), vals=np.asarray(vals),
                radius=cfg.exclusion_radius,
            ))
        return out


class CompileCounter:
    """Backend compiles and persistent-cache loads of this process (one
    instance: JAX's listeners cannot be removed)."""

    _instance = None

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def __init__(self):
        import jax

        self.compiles = 0
        self.cache_hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def total(self) -> int:
        with self._lock:
            return self.compiles + self.cache_hits


# -------------------------------------------------------------------- run


def enable_cache() -> None:
    """JAX's persistent compilation cache at the checkout's fixed
    ``.jax_cache``, whatever the environment names, with every program kept
    (also those that compile in under a second) and none evicted, so that
    only a cell's first run in a checkout compiles."""
    import jax

    from repro.compile_cache import enable_persistent_cache

    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    jax.config.update("jax_enable_x64", True)  # as repro.core sets it
    enable_persistent_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)  # no eviction


def check_device(chips: int):
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoDevice(f"needs a TPU; JAX's first device is on platform "
                       f"{dev.platform!r}")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips; found {len(devices)}")
    return devices


def misplaced(jobs, servers, devices) -> int:
    """Jobs not on the replica they were placed on (job j on replica j mod
    their number), after the window: a job counts where a decision of it
    was answered by another replica (its lease moved), where it is resident
    on any replica but its own or not on its own, or where its posterior's
    ``chol``, ``chol_inv`` and ``alpha`` are not all on its replica's
    chip."""
    replica_of = {server.address: r for r, server in enumerate(servers)}
    bad = 0
    for j, job in enumerate(jobs):
        r = j % len(servers)
        answered = {replica_of.get(d["replica"]) for d in job.decisions}
        resident = [i for i, server in enumerate(servers)
                    if job.name in server.service._jobs]
        handle = servers[r].service._jobs.get(job.name)
        post = None if handle is None else handle.suggester.cache.post
        on_chip = post is not None and all(
            set(a.devices()) == {devices[r]}
            for a in (post.chol, post.chol_inv, post.alpha))
        if answered != {r} or resident != [r] or not on_chip:
            bad += 1
    return bad


def run_cell(cell, cfg, mix, limits, seed, seconds, trace, *,
             require_tpu=True, control=False, log=print):
    """One run of ``cell``; returns the result line's object (with
    ``control`` also the control's readings, under ``control``)."""
    import jax
    import numpy as np

    from bench import spec
    from bench import trace as tracing
    from bench.reference import decision_gaps, encode, fit_gap, standardize
    from bench.traffic import clients
    from repro.core import telemetry
    from repro.distributed import EngineServer

    chips = cell["chips"]
    if require_tpu:
        devices = check_device(chips)
    else:
        devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips}
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")

    space = cfg["space"]
    # the engine's registry, with room for every span of the window
    registry, prior = telemetry.Telemetry(trace_capacity=TRACE_CAPACITY), \
        telemetry._GLOBAL
    telemetry._GLOBAL = registry
    recorder = Recorder()
    counter = CompileCounter.get()
    servers, jobs_client = [], None
    try:
        # one replica per chip, in this process
        for r in range(chips):
            servers.append(EngineServer(
                service_config=spec.service_config(cfg),
                lease_ttl=cfg["server"]["lease_ttl"],
                device=devices[r]).start())
        jobs_client = clients(cfg, mix, seed, [s.address for s in servers])
        # set-up finishes with every job at its barrier; check the kernel
        # while they wait, then open the window
        jobs_client.ready()
        args = recorder.last_setup_call
        if require_tpu:
            if args is None or "tpu_custom_call" not in recorder.acq.lower(
                    *args).as_text():
                raise RuntimeError("optimize_acquisition as lowered for the "
                                   "chip holds no tpu_custom_call")
            log("optimize_acquisition holds tpu_custom_call")
        recorder.start(seed, mix["sample_calls"])
        logdir = None
        if trace:
            registry.set_enabled(True)
            logdir = tempfile.mkdtemp(prefix="bench-trace-")
        setup_s = time.monotonic() - T_START
        compiles0 = counter.total()
        t0 = time.monotonic()
        jobs_client.go(t0 + seconds)
        if trace:
            # host annotations and runtime events, not every Python call
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(logdir, profiler_options=options)
            with jax.profiler.TraceAnnotation(tracing.WINDOW):
                time.sleep(min(mix["trace_seconds"], seconds))
            jax.profiler.stop_trace()
        t1, jobs = jobs_client.finish()
        compiles = counter.total() - compiles0
        recorder.stop()
        registry.set_enabled(False)
        log(f"window: {t1 - t0:.3f} s; compilations inside it: {compiles}")
        device["memory_peak_bytes"] = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices[:chips])
        calls = recorder.calls()
        spans = [e for e in registry.trace_events()
                 if e["kind"] == "span" and e["t0"] >= t0 and e["t1"] <= t1]
        counters = registry.metrics()["counters"]
        placed = misplaced(jobs, servers, devices) if chips > 1 else None
    finally:
        if jobs_client is not None:
            jobs_client.close()
        for server in servers:
            server.shutdown()
        recorder.uninstall()
        telemetry._GLOBAL = prior

    decisions = [d for job in jobs for d in job.decisions if d["t0"] >= t0]
    attempted = len(decisions)
    failed = sum(1 for d in decisions if "error" in d)
    for d in decisions:
        if "error" in d:
            log(f"failed decision: {d['error']}")
    on_time = sum(1 for d in decisions if d["t1"] <= t0 + seconds)
    ms = [(d["t1"] - d["t0"]) * 1e3 for d in decisions if "error" not in d]
    if ms:
        log(f"decisions: {attempted}, {on_time} returned by the deadline; "
            f"latency p50 {np.percentile(ms, 50):.3f} ms, "
            f"p90 {np.percentile(ms, 90):.3f} ms")

    # --- correctness: sampled slots against the reference, on the rows and
    # pending trials of the client's own record; every config
    by_job = {job.name: job for job in jobs}
    gaps, ctrl, fits = {}, {}, {}
    for call in calls:
        job = by_job[call["job"]]
        dec = job.by_ordinal.get(call["ordinal"])
        if dec is None or "error" in dec or dec["n_rows"] != call["n"]:
            # the service answered a request the client did not make, or
            # over other rows than the client had pushed
            gaps["unmatched"] = gaps.get("unmatched", 0) + 1
            continue
        n = dec["n_rows"]
        rows = (job.rows_x[:n], job.rows_y[:n],
                dec["pending"] + dec["configs"][:call["slot"]])
        for key, value in decision_gaps(call, *rows, space).items():
            gaps[key] = max(gaps.get(key, 0.0), value)
        # the draws: whose fit they are, and how well they fit its rows
        gaps["draw_source"] = max(gaps.get("draw_source", 0.0),
                                  call["draw_source"])
        if "fit_gap" in limits and call["fit"] is not None:
            if call["fit"] not in fits:
                fit_job, fit_n = call["fit"]
                src = by_job.get(fit_job)
                fits[call["fit"]] = math.inf if src is None else fit_gap(
                    np.stack([encode(space, c) for c in src.rows_x[:fit_n]]),
                    standardize(np.asarray(src.rows_y[:fit_n], np.float64)),
                    call["params"])
            gaps["fit_gap"] = max(fits.values())
        if control:
            for key, value in decision_gaps(call, *rows, space,
                                            control=True).items():
                ctrl[key] = max(ctrl.get(key, 0.0), value)
    readings = dict(gaps)
    readings.setdefault("unmatched", 0)
    readings["bad_configs"] = sum(job.bad_configs(t0) for job in jobs)
    readings["failed"] = failed
    limits = dict(limits, unmatched=0, failed=0)
    if placed is not None:
        readings["misplaced"] = placed
        limits.setdefault("misplaced", 0)
    # a number missing or not finite reads as the largest float (JSON has
    # no infinity)
    checks = {k: {"value": min(readings.get(k, sys.float_info.max),
                               sys.float_info.max), "limit": v}
              for k, v in limits.items()}
    correct = (bool(calls) and attempted > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))

    # --- metrics
    device_trace = None
    if trace:
        try:
            device_trace = tracing.reduce(tracing.load(logdir), chips,
                                          spec.kernels())
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        device["busy_s"] = device_trace["busy_s"]
        device["window_s"] = device_trace["window_s"]
    try:
        peaks = spec.peaks(dev.device_kind)
    except spec.SpecError:
        if require_tpu:
            raise
        peaks = None
    # what the metric readers read
    run = types.SimpleNamespace(
        decisions=decisions, window=(t0, t1), deadline=t0 + seconds,
        spans=spans, counters=counters, trace=device_trace, peaks=peaks,
        setup_s=setup_s, replicas=chips)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.cell_metrics(cell["name"], kind):
        value = spec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if device_trace is not None:
        result["breakdown"] = {
            "device_ops": [list(kv) for kv in device_trace["device_ops"][:10]],
            "idle_gaps": [list(kv) for kv in device_trace["idle_gaps"][:10]],
        }
    if control:
        result["control"] = ctrl
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no src/repro in {ROOT}; run it from a checkout of the "
              "repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from bench import spec

    cell = spec.workload(args.workload)
    cfg = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    limits = spec.limits(cell["name"])

    try:
        check_device(cell["chips"])
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    enable_cache()

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    result = run_cell(cell, cfg, mix, limits, args.seed, args.seconds,
                      bool(args.trace), log=log)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
