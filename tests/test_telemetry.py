"""Telemetry layer: registry semantics (counters/gauges/histograms/spans),
the bounded trace ring, and — the load-bearing part — non-invasiveness:
telemetry-on and telemetry-off runs produce bit-identical suggestion
streams (in-process and over the socket), and no telemetry key ever rides
an engine snapshot or suggester ``state_dict``."""

import json
import math
import threading

import pytest

from repro.core import (
    BOConfig,
    Continuous,
    SearchSpace,
    SelectionService,
    ServiceConfig,
)
from repro.core import telemetry
from repro.core.gp.slice_sampler import SliceSamplerConfig
from repro.core.telemetry import Telemetry, enabled_from_env

_CFG = BOConfig(
    num_init=3,
    slice_config=SliceSamplerConfig(num_samples=4, burn_in=2, thin=1),
    refit_every=3,
    incremental=True,
)


def _space():
    return SearchSpace([
        Continuous("x", 0.0, 1.0),
        Continuous("y", -1.0, 1.0),
    ])


def _obj(cfg):
    return float((cfg["x"] - 0.3) ** 2 + (cfg["y"] - 0.1) ** 2)


def _drive(handle, steps, start=0):
    stream = []
    for i in range(start, start + steps):
        c = handle.suggest_batch(1)[0]
        stream.append(c)
        handle.store.mark_pending(i, c)
        handle.store.clear_pending(i)
        handle.store.push(c, _obj(c))
    return stream


@pytest.fixture(autouse=True)
def _clean_global_registry():
    """Each test starts and ends with the process-global registry cold and
    disabled, so counter assertions never see another test's writes."""
    telemetry.get().reset()
    telemetry.set_enabled(False)
    yield
    telemetry.get().reset()
    telemetry.set_enabled(False)


class _Ticker:
    def __init__(self, step=1.0):
        self.t = 0.0
        self.step = step

    def __call__(self):
        self.t += self.step
        return self.t


# ---------------------------------------------------------------- registry


class TestRegistry:
    def test_disabled_is_a_noop(self):
        t = Telemetry(enabled=False)
        t.count("a")
        t.gauge("g", 1.0)
        t.observe("h", 0.5)
        t.event("e")
        with t.span("s"):
            pass
        m = t.metrics()
        assert m["counters"] == {} and m["gauges"] == {}
        assert m["histograms"] == {} and t.trace_events() == []

    def test_disabled_span_is_shared_noop(self):
        t = Telemetry(enabled=False)
        assert t.span("a") is t.span("b")  # no per-call allocation

    def test_counters_and_gauges(self):
        t = Telemetry(enabled=True)
        t.count("calls")
        t.count("calls", 2)
        t.gauge("bytes", 10.0)
        t.gauge("bytes", 7.0)  # gauges keep the latest value
        m = t.metrics()
        assert m["counters"] == {"calls": 3}
        assert m["gauges"] == {"bytes": 7.0}

    def test_histogram_log_buckets_and_exact_stats(self):
        t = Telemetry(enabled=True)
        for v in (0.5, 0.5, 3.0, 0.0):
            t.observe("h", v)
        h = t.metrics()["histograms"]["h"]
        assert h["count"] == 4
        assert h["sum"] == pytest.approx(4.0)
        assert h["min"] == 0.0 and h["max"] == 3.0
        # 0.5 -> le_2^-1, 3.0 -> le_2^2, 0.0 -> the underflow bucket
        assert h["buckets"]["le_2^-1"] == 2
        assert h["buckets"]["le_2^2"] == 1
        assert h["buckets"][f"le_2^{-24}"] == 1

    def test_histogram_extreme_values_clamp_to_edge_buckets(self):
        t = Telemetry(enabled=True)
        t.observe("h", 1e-12)
        t.observe("h", 1e12)
        b = t.metrics()["histograms"]["h"]["buckets"]
        assert b[f"le_2^{-24}"] == 1 and b["le_2^24"] == 1

    def test_span_nesting_parent_edges(self):
        t = Telemetry(enabled=True, clock=_Ticker())
        with t.span("outer", job="j"):
            with t.span("inner"):
                pass
            t.event("mark", n=3)
        events = {e["name"]: e for e in t.trace_events()}
        outer, inner, mark = events["outer"], events["inner"], events["mark"]
        assert outer["parent_id"] is None
        assert inner["parent_id"] == outer["span_id"]
        assert mark["parent_id"] == outer["span_id"]
        assert outer["attrs"] == {"job": "j"} and mark["attrs"] == {"n": 3}
        assert outer["t1"] > outer["t0"] and inner["dur"] > 0
        # durations also feed the span.<name> histograms
        hists = t.metrics()["histograms"]
        assert hists["span.outer"]["count"] == 1
        assert hists["span.inner"]["count"] == 1

    def test_span_stack_is_thread_local(self):
        t = Telemetry(enabled=True)
        seen = {}

        def other():
            with t.span("bg"):
                pass

        with t.span("fg"):
            th = threading.Thread(target=other)
            th.start()
            th.join()
        events = {e["name"]: e for e in t.trace_events()}
        assert events["bg"]["parent_id"] is None  # not a child of "fg"
        assert events["bg"]["thread"] != events["fg"]["thread"]
        del seen

    def test_trace_ring_is_bounded(self):
        t = Telemetry(enabled=True, trace_capacity=8)
        for i in range(20):
            t.event("e", i=i)
        events = t.trace_events()
        assert len(events) == 8
        assert [e["attrs"]["i"] for e in events] == list(range(12, 20))

    def test_span_records_on_exception(self):
        t = Telemetry(enabled=True)
        with pytest.raises(ValueError):
            with t.span("boom"):
                raise ValueError("x")
        assert [e["name"] for e in t.trace_events()] == ["boom"]

    def test_export_trace_jsonl_roundtrip(self, tmp_path):
        t = Telemetry(enabled=True, clock=_Ticker())
        with t.span("a", k=1):
            t.event("b")
        path = tmp_path / "trace.jsonl"
        n = t.export_trace(str(path))
        assert n == 2
        lines = [json.loads(l) for l in path.read_text().splitlines()]
        assert {e["name"] for e in lines} == {"a", "b"}

    def test_reset_clears_everything(self):
        t = Telemetry(enabled=True)
        t.count("c")
        t.observe("h", 1.0)
        with t.span("s"):
            pass
        t.reset()
        m = t.metrics()
        assert m["counters"] == {} and m["histograms"] == {}
        assert t.trace_events() == []

    def test_render_text_smoke(self):
        t = Telemetry(enabled=True)
        t.count("c")
        t.gauge("g", 2.5)
        t.observe("h", 1.0)
        text = t.render_text()
        assert "c = 1" in text and "g = 2.5" in text and "h:" in text

    def test_enabled_from_env(self, monkeypatch):
        for val, want in (
            ("1", True), ("true", True), ("ON", True), ("yes", True),
            ("0", False), ("", False), ("off", False),
        ):
            monkeypatch.setenv(telemetry.ENV_FLAG, val)
            assert enabled_from_env() is want
        monkeypatch.delenv(telemetry.ENV_FLAG)
        assert enabled_from_env() is False


# ---------------------------------------------------- non-invasiveness


class TestNonInvasive:
    def test_streams_bit_identical_in_process(self):
        """The whole contract: telemetry-on and telemetry-off services with
        the same seed produce byte-equal suggestion streams and end in
        byte-equal suggester states."""
        space = _space()
        telemetry.set_enabled(False)
        a = SelectionService(ServiceConfig())
        ha = a.register_job("job", space, bo_config=_CFG, seed=11)
        stream_off = _drive(ha, 8)

        telemetry.set_enabled(True)
        b = SelectionService(ServiceConfig())
        hb = b.register_job("job", space, bo_config=_CFG, seed=11)
        stream_on = _drive(hb, 8)

        assert stream_on == stream_off
        assert json.dumps(ha.suggester.state_dict(), sort_keys=True) == \
            json.dumps(hb.suggester.state_dict(), sort_keys=True)
        # and the instrumented run actually recorded something
        m = telemetry.get().metrics()
        assert m["histograms"]["span.suggest.decide"]["count"] == 8
        assert m["histograms"]["span.service.suggest_batch"]["count"] == 8

    def test_no_telemetry_keys_in_snapshots_or_state(self):
        """Counters/spans/traces must never ride engine state: a restored
        engine starts cold. Checked over the full JSON image of both the
        service snapshot and the suggester state_dict, with telemetry live
        and recording while they are taken."""
        telemetry.set_enabled(True)
        svc = SelectionService(ServiceConfig())
        h = svc.register_job("job", _space(), bo_config=_CFG, seed=3)
        _drive(h, 6)
        snap_image = json.dumps(
            svc.snapshot_job("job", include_factors=True), sort_keys=True
        ).lower()
        state_image = json.dumps(
            h.suggester.state_dict(), sort_keys=True
        ).lower()
        for token in ("telemetry", '"span', '"trace', "span_id", "trace_events"):
            assert token not in snap_image
            assert token not in state_image

    def test_arena_and_pool_instrumentation_records(self):
        telemetry.set_enabled(True)
        svc = SelectionService(ServiceConfig())
        h = svc.register_job("job", _space(), bo_config=_CFG, seed=1)
        _drive(h, 5)
        m = telemetry.get().metrics()
        hits = m["counters"].get("service.pool.hit", 0)
        misses = m["counters"].get("service.pool.miss", 0)
        assert hits + misses == 5  # every decision classified exactly once
        assert "arena.resident_bytes" in m["gauges"]

    def test_trace_phase_tree_covers_decision_phases(self):
        """A real decision's span tree: service root -> suggest.decide ->
        posterior/acq/dedup children, linked by parent edges."""
        telemetry.set_enabled(True)
        svc = SelectionService(ServiceConfig())
        h = svc.register_job("job", _space(), bo_config=_CFG, seed=2)
        _drive(h, 4)
        events = telemetry.get().trace_events()
        by_id = {e["span_id"]: e for e in events}
        names = {e["name"] for e in events}
        assert {"service.suggest_batch", "suggest.decide",
                "suggest.acq_opt", "suggest.dedup"} <= names
        decide = [e for e in events if e["name"] == "suggest.decide"]
        assert all(
            by_id[e["parent_id"]]["name"] == "service.suggest_batch"
            for e in decide
        )
        acq = [e for e in events if e["name"] == "suggest.acq_opt"]
        assert all(
            by_id[e["parent_id"]]["name"] == "suggest.decide" for e in acq
        )

    def test_streams_bit_identical_over_socket(self):
        """Socket-served suggestions with telemetry recording on every hop
        (client counters, per-verb server spans, engine spans) equal the
        quiet in-process stream byte-for-byte."""
        from repro.distributed.engine_client import RemoteService
        from repro.distributed.engine_server import EngineServer

        space = _space()
        telemetry.set_enabled(False)
        svc = SelectionService(ServiceConfig())
        h = svc.register_job("job", space, bo_config=_CFG, seed=5)
        ref = _drive(h, 8)

        telemetry.set_enabled(True)
        with EngineServer() as server:
            rsvc = RemoteService([server.address])
            rh = rsvc.register_job("job", space, bo_config=_CFG, seed=5)
            got = _drive(rh, 8)
            rh.close()
        assert got == ref
        m = telemetry.get().metrics()
        assert m["counters"]["server.rpc.suggest_batch"] == 8
        assert m["histograms"]["span.rpc.suggest_batch"]["count"] == 8

    def test_metrics_rpc_verb_live_replica(self):
        """The read-only metrics verb: no job, no lease, serves the
        replica's live registry plus service stats."""
        from repro.distributed.engine_client import RemoteService
        from repro.distributed.engine_server import EngineServer

        telemetry.set_enabled(True)
        with EngineServer() as server:
            rsvc = RemoteService([server.address])
            rh = rsvc.register_job("job", _space(), bo_config=_CFG, seed=1)
            _drive(rh, 4)
            dump = rsvc.fetch_metrics()
            rh.close()
        counters = dump["metrics"]["counters"]
        assert counters["server.rpc.suggest_batch"] == 4
        assert counters["server.rpc.register"] == 1
        assert dump["metrics"]["histograms"]["span.rpc.suggest_batch"]["count"] == 4
        assert dump["service_stats"]["groups"][0]["jobs"] == ["job"]
        # frame accounting saw every request and reply
        assert dump["metrics"]["histograms"]["span.service.suggest_batch"]["count"] == 4

    def test_no_telemetry_keys_in_wire_snapshot(self):
        """The snapshot a failover replays from — fetched over the wire,
        with telemetry live — carries no telemetry keys either."""
        from repro.distributed.engine_client import RemoteService
        from repro.distributed.engine_server import EngineServer

        telemetry.set_enabled(True)
        with EngineServer() as server:
            rsvc = RemoteService([server.address])
            rh = rsvc.register_job("job", _space(), bo_config=_CFG, seed=9)
            _drive(rh, 5)
            snap = rh.fetch_snapshot(include_factors=True)
            rh.close()
        image = json.dumps(snap, sort_keys=True).lower()
        for token in ("telemetry", '"span', '"trace', "span_id"):
            assert token not in image

    def test_span_overhead_bounded_while_disabled(self):
        """Disabled instrumentation must be ~free: a span site while off is
        just an attribute load and a flag test. This guards the hot path
        against an accidental always-on allocation, not a precise SLO
        (the ≤5 % enabled-overhead budget is checked on the bench)."""
        telemetry.set_enabled(False)
        import timeit

        base = timeit.timeit(lambda: None, number=20000)
        spans = timeit.timeit(
            lambda: telemetry.span("x").__enter__(), number=20000
        )
        # generous: merely "same order of magnitude as an empty call"
        assert spans < base * 60 + 0.05


# ------------------------------------------------------------ obs_report


class TestObsReport:
    def _tools_main(self):
        import sys
        from pathlib import Path

        repo = Path(__file__).resolve().parents[1]
        if str(repo) not in sys.path:  # conftest only inserts src/
            sys.path.insert(0, str(repo))
        from tools.obs_report import main

        return main

    def test_renders_real_multi_job_run(self, tmp_path, capsys):
        """Acceptance: phase breakdown + per-decision trees + job timeline
        rendered from the trace of a real two-job service run."""
        main = self._tools_main()
        telemetry.set_enabled(True)
        svc = SelectionService(ServiceConfig())
        ha = svc.register_job("job-a", _space(), bo_config=_CFG, seed=1)
        hb = svc.register_job("job-b", _space(), bo_config=_CFG, seed=2)
        _drive(ha, 4)
        _drive(hb, 3)
        trace = tmp_path / "trace.jsonl"
        metrics = tmp_path / "metrics.json"
        telemetry.get().export_trace(str(trace))
        metrics.write_text(json.dumps(telemetry.get().metrics()))

        rc = main([str(trace), "--metrics", str(metrics)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "phase breakdown" in out
        for phase in ("service.suggest_batch", "suggest.decide",
                      "suggest.acq_opt", "suggest.dedup"):
            assert phase in out
        assert "job timeline" in out
        assert "job=job-a" in out and "job=job-b" in out
        assert "slowest" in out  # per-decision span trees
        assert "counter  service.pool." in out or "counter  suggest." in out

    def test_job_filter_restricts_to_one_job(self, tmp_path, capsys):
        main = self._tools_main()
        telemetry.set_enabled(True)
        svc = SelectionService(ServiceConfig())
        ha = svc.register_job("job-a", _space(), bo_config=_CFG, seed=1)
        hb = svc.register_job("job-b", _space(), bo_config=_CFG, seed=2)
        _drive(ha, 3)
        _drive(hb, 3)
        trace = tmp_path / "trace.jsonl"
        telemetry.get().export_trace(str(trace))

        rc = main([str(trace), "--job", "job-b", "--decisions", "0"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "job=job-b" in out and "job=job-a" not in out

    def test_empty_trace_exits_nonzero(self, tmp_path, capsys):
        main = self._tools_main()
        trace = tmp_path / "trace.jsonl"
        trace.write_text("")
        assert main([str(trace)]) == 1
        assert "empty trace" in capsys.readouterr().out


# ------------------------------------------------- client observability


class TestClientObservability:
    def test_failed_heartbeat_is_counted_and_logged_then_fails_over(self, caplog):
        """Regression for the silent renewal swallow: a background renewal
        that cannot reach any replica increments ``client.heartbeat_error``
        and logs a warning — and the handle still fails over correctly on
        the next real request once a replica is reachable again."""
        import logging

        from repro.distributed.engine_client import RemoteService
        from repro.distributed.engine_server import EngineServer

        telemetry.set_enabled(True)
        space = _space()
        s1 = EngineServer().start()
        rsvc = RemoteService([s1.address])
        rh = rsvc.register_job("job", space, bo_config=_CFG, seed=4)
        _drive(rh, 4)
        before = dict(telemetry.get().metrics()["counters"])
        assert "client.heartbeat_error" not in before

        s1.shutdown()  # stop accepting, then sever the live connection
        rh._conn.close()  # (shutdown alone leaves established conns up)
        with caplog.at_level(logging.WARNING, "repro.distributed.engine_client"):
            rh._renew_once()  # the renewer's per-tick body
        counters = telemetry.get().metrics()["counters"]
        assert counters["client.heartbeat_error"] == 1
        assert any(
            "lease renewal failed" in r.message for r in caplog.records
        )

        # a replacement replica joins the fleet: the next *real* request
        # re-adopts from the last snapshot and the stream continues
        s2 = EngineServer().start()
        try:
            rsvc.addresses.append(s2.address)
            more = _drive(rh, 2, start=4)
            assert len(more) == 2
            after = telemetry.get().metrics()["counters"]
            assert after.get("client.failover", 0) >= 1
            assert after.get("client.readopt", 0) >= 1
            rh.close()
        finally:
            s2.shutdown()

    def test_oplog_replay_length_recorded(self):
        """A re-adoption that replays logged ops records the replay length."""
        from repro.distributed.engine_client import RemoteService
        from repro.distributed.engine_server import EngineServer

        telemetry.set_enabled(True)
        space = _space()
        s1 = EngineServer().start()
        s2 = EngineServer().start()
        try:
            # big snapshot_every keeps ops in the log instead of refreshing
            rsvc = RemoteService([s1.address, s2.address], snapshot_every=100)
            rh = rsvc.register_job("job", space, bo_config=_CFG, seed=2)
            _drive(rh, 3)
            s1.shutdown()
            rh._conn.close()  # sever the live connection as well
            _drive(rh, 2, start=3)  # failover -> readopt -> replay
            m = telemetry.get().metrics()
            assert m["counters"].get("client.oplog.replayed_ops", 0) > 0
            assert m["histograms"]["client.oplog.replay_len"]["count"] >= 1
            rh.close()
        finally:
            s2.shutdown()


# ------------------------------------------------- the profiler's clock


def _host_events(logdir):
    """Each ``/host:CPU`` line of the profiler trace under ``logdir``:
    [(name, start_ns, end_ns)]."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    data = ProfileData.from_file(path)
    return [
        [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
         for e in line.events]
        for plane in data.planes if plane.name == "/host:CPU"
        for line in plane.lines
    ]


class TestProfilerClock:
    def test_spans_mirror_into_the_profiler_trace(self, tmp_path):
        """A live span is a profiler annotation of its name (attributes
        left out), nested as the spans nest; a span recorded while off
        is not."""
        import time

        import jax

        t = Telemetry(enabled=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            with t.span("mirror.outer", job="j"):
                with t.span("mirror.inner", slot=0):
                    time.sleep(0.002)
            t.set_enabled(False)
            with t.span("mirror.off"):
                time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
        lines = _host_events(tmp_path)
        (line,) = [ln for ln in lines
                   if any(n == "mirror.outer" for n, _, _ in ln)]
        outer = next(e for e in line if e[0] == "mirror.outer")
        inner = next(e for e in line if e[0] == "mirror.inner")
        assert outer[1] <= inner[1] and inner[2] <= outer[2]
        assert inner[2] - inner[1] >= 2_000_000
        names = {n for ln in lines for n, _, _ in ln}
        assert "mirror.off" not in names
        assert not any("job=" in n or "slot=" in n for n in names)

    def test_acq_opt_holds_the_read_of_the_candidates(self, monkeypatch):
        """``suggest.acq_opt`` ends once the candidates are on the host: a
        slow read of them lands in it, not in ``suggest.dedup``."""
        import time

        import numpy as np

        import repro.core.suggest as suggest_mod

        real = suggest_mod.optimize_acquisition

        class Slow:
            def __init__(self, arr):
                self.arr = arr

            def __array__(self, dtype=None, copy=None):
                time.sleep(0.05)
                return np.asarray(self.arr, dtype=dtype)

        def slow_acq(*args):
            cands, vals = real(*args)
            return Slow(cands), vals

        h = SelectionService(ServiceConfig()).register_job(
            "job", _space(), bo_config=_CFG, seed=7)
        _drive(h, 4)  # past the cold start, programs compiled
        monkeypatch.setattr(suggest_mod, "optimize_acquisition", slow_acq)
        telemetry.set_enabled(True)
        _drive(h, 1, start=4)
        events = telemetry.get().trace_events()
        (acq,) = [e for e in events if e["name"] == "suggest.acq_opt"]
        (dedup,) = [e for e in events if e["name"] == "suggest.dedup"]
        assert acq["parent_id"] == dedup["parent_id"]
        assert acq["dur"] >= 0.05 > dedup["dur"]

    def test_lock_wait_is_a_sibling_of_the_decision(self):
        """``server.lock_wait`` is a child of ``rpc.<verb>`` that closes
        before the dispatch's spans open; with two clients contending and
        the lock held from outside for 100 ms, the wait is measured."""
        import time

        from repro.distributed.engine_client import RemoteService
        from repro.distributed.engine_server import EngineServer

        telemetry.set_enabled(True)
        with EngineServer() as server:
            handles = [
                RemoteService([server.address]).register_job(
                    f"job-{i}", _space(), bo_config=_CFG, seed=i)
                for i in range(2)
            ]
            for h in handles:
                _drive(h, 4)
            telemetry.get().reset()
            results = []
            threads = [
                threading.Thread(
                    target=lambda h=h: results.append(_drive(h, 2, start=4)))
                for h in handles
            ]
            with server._lock:
                for th in threads:
                    th.start()
                time.sleep(0.1)
            for th in threads:
                th.join()
            for h in handles:
                h.close()
        assert len(results) == 2
        events = telemetry.get().trace_events()
        by_id = {e["span_id"]: e for e in events}
        rpcs = [e for e in events if e["name"] == "rpc.suggest_batch"]
        assert len(rpcs) == 4
        waits = [e for e in events if e["name"] == "server.lock_wait"
                 and by_id[e["parent_id"]]["name"] == "rpc.suggest_batch"]
        assert sorted(w["parent_id"] for w in waits) == sorted(
            r["span_id"] for r in rpcs)
        assert all(w["attrs"] == {"verb": "suggest_batch"} for w in waits)
        service = [e for e in events if e["name"] == "service.suggest_batch"]
        assert len(service) == 4
        for s in service:
            rpc = by_id[s["parent_id"]]
            assert rpc["name"] == "rpc.suggest_batch"
            (wait,) = [w for w in waits if w["parent_id"] == rpc["span_id"]]
            assert wait["t1"] <= s["t0"]
        assert max(w["dur"] for w in waits) >= 0.09

    def test_compile_is_counted_under_the_open_span(self):
        """A compile inside a span counts ``jax.compiles`` and records a
        ``jax.compile`` event parented to that span, in the registry that
        is current at the compile, after ``_GLOBAL`` was swapped."""
        import jax
        import jax.numpy as jnp

        telemetry.set_enabled(True)  # the first enable registers
        prior = telemetry._GLOBAL
        swapped = Telemetry(enabled=True)
        telemetry._GLOBAL = swapped
        try:
            fresh = jax.jit(lambda x: jnp.sin(x) * 3.25 + 0.5)
            with swapped.span("decide"):
                fresh(jnp.arange(5.0)).block_until_ready()
        finally:
            telemetry._GLOBAL = prior
        events = swapped.trace_events()
        (decide,) = [e for e in events if e["name"] == "decide"]
        compiles = [e for e in events if e["name"] == "jax.compile"]
        assert swapped.metrics()["counters"]["jax.compiles"] == len(compiles)
        assert any(e["parent_id"] == decide["span_id"] for e in compiles)
        assert all(e["attrs"]["secs"] >= 0.0 for e in compiles)
        assert "jax.compiles" not in prior.metrics()["counters"]

    @pytest.mark.parametrize("full_tracebacks", [True, False])
    def test_acquisition_stages_named_in_the_compiled_program(
            self, full_tracebacks):
        """Every stage of the acquisition program carries its scope into
        the compiled instructions' ``op_name``, also when locations are
        written without full tracebacks, as the entry points that use the
        persistent compilation cache write them."""
        import re

        import jax
        import jax.numpy as jnp

        from repro.core.gp.gp import GPPosterior
        from repro.core.gp.params import GPHyperParams
        from repro.core.optimize_acq import AcqOptConfig, optimize_acquisition

        s, n, d = 2, 8, 2
        params = GPHyperParams(jnp.zeros((s, d)), jnp.zeros(s), jnp.zeros(s),
                               jnp.zeros((s, d)), jnp.zeros((s, d)))
        eye = jnp.broadcast_to(jnp.eye(n), (s, n, n))
        post = GPPosterior(x_train=jnp.zeros((n, d)), mask=jnp.ones(n, bool),
                           chol=eye, alpha=jnp.zeros((s, n)), params=params,
                           chol_inv=eye)
        cfg = AcqOptConfig(num_anchors=16, num_refine=2, refine_steps=2)
        name = "jax_include_full_tracebacks_in_locations"
        prev = getattr(jax.config, name)
        jax.config.update(name, full_tracebacks)
        try:
            text = optimize_acquisition.lower(
                post, jnp.zeros((16, d)), jnp.asarray(0.0), jnp.zeros((4, d)),
                jnp.zeros(4, bool), jax.random.PRNGKey(0), cfg,
            ).compile().as_text()
        finally:
            jax.config.update(name, prev)
        scopes = [next((p for p in op.split("/") if p.startswith("acq.")), None)
                  for op in re.findall(r'op_name="([^"]*)"', text)]
        assert {"acq.anchors", "acq.refine", "acq.rerank"} <= set(scopes)
        assert scopes.count("acq.refine") > len(scopes) // 2


class TestRefinePath:
    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    def test_each_dispatch_counts_the_cached_inverse_path(self, backend):
        """Every acquisition dispatch counts ``acq.refine.cached_inverse``
        once, and none counts ``acq.refine.solve``: the engine's factor
        carries L⁻¹ whatever the scoring backend."""
        import dataclasses

        from repro.core.optimize_acq import AcqOptConfig

        cfg = dataclasses.replace(
            _CFG, acq=AcqOptConfig(num_anchors=64, backend=backend))
        h = SelectionService(ServiceConfig()).register_job(
            "job", _space(), bo_config=cfg, seed=5)
        telemetry.set_enabled(True)
        _drive(h, 6)
        dispatches = [e for e in telemetry.get().trace_events()
                      if e["name"] == "suggest.acq_opt"]
        counters = telemetry.get().metrics()["counters"]
        refine = {k: v for k, v in counters.items()
                  if k.startswith("acq.refine.")}
        assert len(dispatches) == 6 - _CFG.num_init
        assert refine == {"acq.refine.cached_inverse": len(dispatches)}
