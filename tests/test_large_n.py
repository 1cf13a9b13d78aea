"""Large-n posterior backend: exact-path bit-identity (in-process and over
the socket), subset-backend invariances (eviction replay, snapshot restore,
boundary rebuild), chunked snapshot frames (unit + n ≥ 10⁴ fresh-process
restore), end-to-end arena budgeting, and per-head GPHP chains."""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (
    BOConfig,
    BOSuggester,
    Continuous,
    MetricSet,
    MetricSpec,
    ObservationStore,
    SearchSpace,
    SelectionService,
    ServiceConfig,
)
from repro.core.gp.slice_sampler import SliceSamplerConfig
from repro.core.gp.sparse import select_inducing
from repro.core.optimize_acq import AcqOptConfig
from repro.core.rpc import (
    bo_config_from_wire,
    bo_config_to_wire,
    decode_snapshot_frame,
    decode_snapshot_frames,
    encode_snapshot_frame,
    encode_snapshot_frames,
)
from repro.distributed.engine_client import RemoteService, _Connection
from repro.distributed.engine_server import EngineServer

_EXACT = BOConfig(
    num_init=3,
    slice_config=SliceSamplerConfig(num_samples=4, burn_in=2, thin=1),
    refit_every=3,
    incremental=True,
)
# identical engine knobs, subset backend active from boundary 12 with a small
# inducing budget — every invariance below runs with selection truly live.
_SUBSET = dataclasses.replace(
    _EXACT, posterior_backend="subset", n_switch=12, max_inducing=10
)


def _space():
    return SearchSpace([
        Continuous("x", 0.0, 1.0),
        Continuous("y", -1.0, 1.0),
    ])


def _obj(cfg):
    return float((cfg["x"] - 0.3) ** 2 + (cfg["y"] - 0.1) ** 2)


def _seeded_store(space, n, seed=3, metrics=None):
    store = ObservationStore(space, metrics=metrics)
    rng = np.random.default_rng(seed)
    for c in space.sample(rng, n):
        if metrics is None:
            store.push(c, _obj(c))
        else:
            store.push_metrics(c, {"loss": _obj(c), "lat": c["x"] + c["y"]})
    return store


def _drive_suggester(sug, store, steps):
    stream = []
    for _ in range(steps):
        c = sug.suggest_batch(1)[0]
        stream.append(c)
        store.push(c, _obj(c))
    return stream


def _drive_handle(handle, steps, start=0):
    stream = []
    for i in range(start, start + steps):
        c = handle.suggest_batch(1)[0]
        stream.append(c)
        handle.store.mark_pending(i, c)
        handle.store.clear_pending(i)
        handle.store.push(c, _obj(c))
    return stream


# ------------------------------------------------------- inducing selection


class TestSelectInducing:
    def test_deterministic_sorted_unique(self):
        rng = np.random.default_rng(0)
        x = rng.random((200, 3))
        a = select_inducing(x, 32)
        b = select_inducing(x.copy(), 32)
        assert np.array_equal(a, b)
        assert len(set(a.tolist())) == 32
        assert np.all(np.diff(a) > 0)  # sorted ascending, no repeats

    def test_small_n_returns_all_rows(self):
        x = np.random.default_rng(1).random((5, 2))
        assert np.array_equal(select_inducing(x, 8), np.arange(5))
        assert np.array_equal(select_inducing(x, 5), np.arange(5))

    def test_duplicates_never_repicked(self):
        # 3 distinct locations, many exact duplicates: the greedy sweep must
        # still return m *distinct row indices*.
        base = np.array([[0.0, 0.0], [1.0, 1.0], [0.5, 0.0]])
        x = np.repeat(base, 10, axis=0)
        sel = select_inducing(x, 6)
        assert len(set(sel.tolist())) == 6

    def test_spreads_over_clusters(self):
        # two tight clusters far apart: a diverse subset must hit both.
        rng = np.random.default_rng(2)
        x = np.concatenate([
            rng.normal(0.0, 0.01, (50, 2)),
            rng.normal(10.0, 0.01, (50, 2)),
        ])
        sel = select_inducing(x, 4)
        assert np.any(sel < 50) and np.any(sel >= 50)

    def test_rejects_bad_m(self):
        with pytest.raises(ValueError):
            select_inducing(np.zeros((4, 2)), 0)


# ----------------------------------------------- exact-path bit-equivalence


class TestExactPathIdentity:
    def test_subset_below_switch_bit_identical_in_process(self):
        """posterior_backend="subset" with n < n_switch must be the exact
        engine bit-for-bit — the auto-switch contract of the PR."""
        space = _space()
        high = dataclasses.replace(_SUBSET, n_switch=4096)
        sta, stb = _seeded_store(space, 8), _seeded_store(space, 8)
        a = BOSuggester(space, _EXACT, seed=5, store=sta)
        b = BOSuggester(space, high, seed=5, store=stb)
        assert _drive_suggester(a, sta, 6) == _drive_suggester(b, stb, 6)

    def test_subset_below_switch_bit_identical_over_socket(self):
        """Same contract across the process boundary: a remote job declared
        with the subset backend (below threshold) reproduces the in-process
        exact engine's stream, pinning the v3 config wire fields too."""
        space = _space()
        svc = SelectionService(ServiceConfig())
        h = svc.register_job("job", space, bo_config=_EXACT, seed=5)
        ref = _drive_handle(h, 6)

        high = dataclasses.replace(_SUBSET, n_switch=4096)
        with EngineServer() as server:
            rsvc = RemoteService([server.address])
            rh = rsvc.register_job("job", space, bo_config=high, seed=5)
            got = _drive_handle(rh, 6)
        assert got == ref


# ------------------------------------------------- subset-backend invariance


class TestSubsetInvariance:
    def test_rebuild_replays_boundary_factorization_bit_exact(self):
        """drop_factors (arena eviction) → next decision rebuilds by
        factorizing the inducing set at the boundary and replaying appends —
        the factor blocks must come back bit-identical, not just close."""
        space = _space()
        store = _seeded_store(space, 20)
        sug = BOSuggester(space, _SUBSET, seed=5, store=store)
        _drive_suggester(sug, store, 2)  # past a boundary + appends
        sug.suggest_batch(1)  # factors now cover every store row
        assert sug.cache.inducing_sel is not None
        before = sug.cache.post
        sel_before = sug.cache.inducing_sel.copy()

        sug.cache.drop_factors()
        c = sug.suggest_batch(1)[0]  # same store state: pure rebuild
        after = sug.cache.post
        assert np.array_equal(np.asarray(before.chol), np.asarray(after.chol))
        assert np.array_equal(np.asarray(before.alpha), np.asarray(after.alpha))
        assert np.array_equal(sel_before, sug.cache.inducing_sel)
        del c

    def test_eviction_invariant_suggestions(self):
        """Tight vs roomy arena budgets: identical subset-backend suggestion
        streams (evictions replay the inducing construction RNG-free)."""

        def run(budget_mb):
            space = _space()
            svc = SelectionService(ServiceConfig(arena_budget_mb=budget_mb))
            h1 = svc.register_job("a", space, bo_config=_SUBSET, seed=5)
            h2 = svc.register_job("b", space, bo_config=_SUBSET, seed=9)
            rng = np.random.default_rng(3)
            for c in space.sample(rng, 18):
                h1.store.push(c, _obj(c))
                h2.store.push(c, _obj(c) + 0.1)
            stream = []
            for _ in range(4):
                c1 = h1.suggest_batch(1)[0]
                h1.store.push(c1, _obj(c1))
                c2 = h2.suggest_batch(1)[0]
                h2.store.push(c2, _obj(c2) + 0.1)
                stream.append((c1, c2))
            return stream, svc

        tight, svc_t = run(1e-6)
        roomy, svc_r = run(1024.0)
        assert svc_t.arena.evictions > 0
        assert svc_r.arena.evictions == 0
        assert tight == roomy

    def test_snapshot_restore_subset_active(self):
        """Engine snapshot taken with the inducing set live → restored into a
        fresh service → identical continuation."""
        space = _space()
        svc = SelectionService(ServiceConfig())
        h = svc.register_job("job", space, bo_config=_SUBSET, seed=5)
        rng = np.random.default_rng(3)
        for c in space.sample(rng, 18):
            h.store.push(c, _obj(c))
        _drive_handle(h, 2, start=100)
        snap = svc.snapshot_job("job")
        assert snap["cache"]["inducing_sel"] is not None
        expected = _drive_handle(h, 3, start=200)

        rh = SelectionService(ServiceConfig()).restore_job(
            json.loads(json.dumps(snap))
        )
        assert _drive_handle(rh, 3, start=200) == expected

    def test_state_dict_roundtrip_subset_active(self):
        space = _space()
        s1 = BOSuggester(space, _SUBSET, seed=5, store=_seeded_store(space, 20))
        s1.suggest_batch(1)
        state = json.loads(json.dumps(s1.state_dict()))
        a = s1.suggest_batch(1)

        s2 = BOSuggester(space, _SUBSET, seed=5, store=_seeded_store(space, 20))
        s2.suggest_batch(1)
        s2.load_state_dict(state)
        assert s2.suggest_batch(1) == a

    @pytest.mark.pallas
    def test_pallas_matches_xla_at_subset_shapes(self):
        """The fused anchor-scoring kernel consumes the subset-sized factor
        unchanged: backend="pallas" picks the same candidates as "xla"."""

        def run(acq_backend):
            space = _space()
            cfg = dataclasses.replace(
                _SUBSET, acq=AcqOptConfig(backend=acq_backend)
            )
            store = _seeded_store(space, 20)
            sug = BOSuggester(space, cfg, seed=5, store=store)
            return _drive_suggester(sug, store, 4)

        assert run("pallas") == run("xla")


# -------------------------------------------------- arena budget end-to-end


class TestArenaBudget:
    def test_stats_report_factor_and_store_bytes(self):
        space = _space()
        svc = SelectionService(ServiceConfig(arena_budget_mb=1024.0))
        h = svc.register_job("job", space, bo_config=_SUBSET, seed=5)
        rng = np.random.default_rng(3)
        for c in space.sample(rng, 14):
            h.store.push(c, _obj(c))
        h.suggest_batch(1)
        stats = svc.arena.stats()
        assert stats["store_bytes"] > 0
        assert stats["factor_bytes"] > 0
        assert stats["resident_bytes"] == (
            stats["factor_bytes"] + stats["store_bytes"]
        )

    def test_resident_bytes_stay_under_budget_multi_job(self):
        """End-to-end budgeting: with a budget sized between one and two
        jobs' factor residency (above the un-evictable store floor), the
        arena must evict and total resident bytes must stay ≤ budget after
        every decision — with suggestion streams unchanged."""

        def run(budget_mb, sample=False):
            space = _space()
            svc = SelectionService(ServiceConfig(arena_budget_mb=budget_mb))
            handles = [
                svc.register_job(f"j{k}", space, bo_config=_SUBSET, seed=5 + k)
                for k in range(2)
            ]
            rng = np.random.default_rng(3)
            for c in space.sample(rng, 18):
                for k, h in enumerate(handles):
                    h.store.push(c, _obj(c) + 0.1 * k)
            stream, samples = [], []
            for _ in range(4):
                for k, h in enumerate(handles):
                    c = h.suggest_batch(1)[0]
                    h.store.push(c, _obj(c) + 0.1 * k)
                    stream.append(c)
                    if sample:
                        samples.append(svc.arena.resident_bytes())
            return stream, samples, svc

        roomy, _, svc_r = run(1024.0)
        per_job_factor = max(
            c.factor_nbytes() for c in svc_r.arena._entries.values()
        )
        store_floor = svc_r.arena.store_bytes()
        budget = store_floor + int(1.5 * per_job_factor)

        tight, samples, svc_t = run(budget / 2**20, sample=True)
        assert svc_t.arena.evictions > 0
        assert tight == roomy
        assert max(samples) <= budget
        assert svc_t.arena.budget_bytes == budget


# -------------------------------------------------- chunked snapshot frames


class TestChunkedFrames:
    def test_roundtrip_matches_single_frame(self):
        snap = {"rows": list(range(500)), "blob": "x" * 4096}
        frames = encode_snapshot_frames(snap, "zlib", 64)
        assert len(frames) > 1
        assert decode_snapshot_frames(frames, "zlib") == snap
        # chunking splits the same compressed stream the single-frame path
        # ships — the joined bytes are identical, not merely equivalent.
        single = encode_snapshot_frame(snap, "zlib")
        assert decode_snapshot_frame(single, "zlib") == snap

    def test_one_frame_when_under_limit(self):
        frames = encode_snapshot_frames({"a": 1}, "zlib", 1 << 20)
        assert len(frames) == 1

    def test_rejects_bad_inputs(self):
        with pytest.raises(ValueError):
            encode_snapshot_frames({}, "zlib", 0)
        with pytest.raises(ValueError):
            encode_snapshot_frames({}, "nope", 64)
        with pytest.raises(ValueError):
            decode_snapshot_frames(["aa"], "nope")

    def test_server_chunks_when_asked(self):
        """Raw-socket check of the negotiated chunked reply shape."""
        from repro.core.rpc import (
            RegisterRequest,
            SnapshotReply,
            SnapshotRequest,
        )

        space = _space()
        with EngineServer() as server:
            conn = _Connection(server.address, 5.0, 60.0)
            reply = conn.call(RegisterRequest(
                job_name="job", space_spec=space.to_spec(), seed=5,
                bo_config=bo_config_to_wire(_EXACT),
            ))
            snap_plain = conn.call(SnapshotRequest(
                job_name="job", lease=reply.lease,
            ))
            snap_chunked = conn.call(SnapshotRequest(
                job_name="job", lease=reply.lease,
                accept_codecs=["zlib"], max_frame_bytes=128,
            ))
            conn.close()
        assert isinstance(snap_chunked, SnapshotReply)
        assert snap_chunked.frames is not None and len(snap_chunked.frames) > 1
        assert (
            decode_snapshot_frames(snap_chunked.frames, snap_chunked.codec)
            == snap_plain.snapshot
        )

    def test_remote_service_chunked_stream_identical(self):
        """A client configured for chunked snapshot fetches produces the
        same suggestion stream as the in-process service — the failover
        baseline travels in frames without touching the decision path."""
        space = _space()
        svc = SelectionService(ServiceConfig())
        h = svc.register_job("job", space, bo_config=_EXACT, seed=5)
        ref = _drive_handle(h, 6)

        with EngineServer() as server:
            rsvc = RemoteService(
                [server.address], snapshot_every=3, snapshot_frame_bytes=512
            )
            rh = rsvc.register_job("job", space, bo_config=_EXACT, seed=5)
            got = _drive_handle(rh, 6)
        assert got == ref

    @pytest.mark.slow
    def test_large_store_chunked_restore_fresh_process(self, tmp_path):
        """n ≥ 10⁴ store → snapshot → chunked zlib frames → *fresh
        interpreter* decodes, restores, and continues the stream exactly."""
        space = _space()
        svc = SelectionService(ServiceConfig())
        cfg = dataclasses.replace(
            _SUBSET, n_switch=512, max_inducing=64, refit_every=64
        )
        h = svc.register_job("job", space, bo_config=cfg, seed=5)
        rng = np.random.default_rng(3)
        xs = rng.random((10_000, 2))
        xs[:, 1] = 2.0 * xs[:, 1] - 1.0
        for i in range(10_000):
            h.store.push_encoded(
                space.encode({"x": float(xs[i, 0]), "y": float(xs[i, 1])}),
                float((xs[i, 0] - 0.3) ** 2 + (xs[i, 1] - 0.1) ** 2),
            )
        c = h.suggest_batch(1)[0]
        h.store.push(c, _obj(c))

        snap = svc.snapshot_job("job")
        frames = encode_snapshot_frames(snap, "zlib", 64 << 10)
        assert len(frames) > 1
        frames_path = tmp_path / "frames.json"
        frames_path.write_text(json.dumps(frames))
        expected = h.suggest_batch(1)[0]

        child = (
            "import json, sys\n"
            "from repro.core.rpc import decode_snapshot_frames\n"
            "from repro.core.service import SelectionService, ServiceConfig\n"
            "snap = decode_snapshot_frames(json.load(open(sys.argv[1])), 'zlib')\n"
            "h = SelectionService(ServiceConfig()).restore_job(snap)\n"
            "print(json.dumps(h.suggest_batch(1)[0]))\n"
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"
        )
        proc = subprocess.run(
            [sys.executable, "-c", child, str(frames_path)],
            capture_output=True, text=True, env=env, timeout=600,
        )
        assert proc.returncode == 0, proc.stderr
        got = json.loads(proc.stdout.strip().splitlines()[-1])
        assert got == expected


# ---------------------------------------------------------- config wire v3


class TestConfigWire:
    def test_new_fields_roundtrip(self):
        blob = json.loads(json.dumps(bo_config_to_wire(_SUBSET)))
        assert bo_config_from_wire(blob) == _SUBSET

    def test_old_blob_gets_defaults(self):
        blob = bo_config_to_wire(_EXACT)
        for key in ("posterior_backend", "n_switch", "max_inducing",
                    "per_head_gphp"):
            del blob[key]
        cfg = bo_config_from_wire(blob)
        assert cfg.posterior_backend == "exact"
        assert cfg.n_switch == 2048
        assert cfg.max_inducing == 1024
        assert cfg.per_head_gphp is False

    def test_backend_validated(self):
        with pytest.raises(ValueError):
            dataclasses.replace(_EXACT, posterior_backend="vortex")
        with pytest.raises(ValueError):
            dataclasses.replace(_EXACT, max_inducing=1)


# ---------------------------------------------------------- per-head GPHPs


_CONSTRAINED = (
    MetricSpec("loss"),
    MetricSpec("lat", objective=False, threshold=0.9),
)


class TestPerHeadGPHP:
    def test_m1_is_a_noop(self):
        """With a single metric there are no extra heads: per_head_gphp=True
        must be bit-identical to the default path."""
        space = _space()
        on = dataclasses.replace(_EXACT, per_head_gphp=True)
        sta, stb = _seeded_store(space, 8), _seeded_store(space, 8)
        a = BOSuggester(space, _EXACT, seed=5, store=sta)
        b = BOSuggester(space, on, seed=5, store=stb)
        assert _drive_suggester(a, sta, 5) == _drive_suggester(b, stb, 5)

    def test_constrained_runs_and_differs_from_shared(self):
        """M=2 constrained job: per-head chains run (their own MCMC per head)
        and generally pick different candidates than the shared-factor path —
        equality here would mean the flag is dead."""

        def run(cfg):
            space = _space()
            ms = MetricSet(list(_CONSTRAINED))
            store = _seeded_store(space, 8, metrics=ms)
            sug = BOSuggester(space, cfg, seed=5, store=store)
            stream = []
            for _ in range(4):
                c = sug.suggest_batch(1)[0]
                stream.append(c)
                store.push_metrics(c, {"loss": _obj(c), "lat": c["x"] + c["y"]})
            return stream

        on = dataclasses.replace(_EXACT, per_head_gphp=True)
        shared = run(_EXACT)
        per_head = run(on)
        assert len(per_head) == 4
        assert shared != per_head

    @pytest.mark.parametrize("per_head", [False, True])
    def test_refine_counter_names_the_solve_path(self, per_head):
        """An M=2 job's refinement reads the cached L⁻¹ of the shared factor
        (``acq.refine.cached_inverse``); per-head factors carry none, so
        with per_head_gphp every dispatch counts ``acq.refine.solve``."""
        from repro.core import telemetry

        space = _space()
        cfg = dataclasses.replace(_EXACT, per_head_gphp=per_head)
        store = _seeded_store(space, 8, metrics=MetricSet(list(_CONSTRAINED)))
        sug = BOSuggester(space, cfg, seed=5, store=store)
        telemetry.get().reset()
        telemetry.set_enabled(True)
        try:
            for _ in range(2):
                c = sug.suggest_batch(1)[0]
                store.push_metrics(c, {"loss": _obj(c), "lat": c["x"] + c["y"]})
            counters = telemetry.get().metrics()["counters"]
        finally:
            telemetry.set_enabled(False)
            telemetry.get().reset()
        path = "acq.refine.solve" if per_head else "acq.refine.cached_inverse"
        refine = {k: v for k, v in counters.items()
                  if k.startswith("acq.refine.")}
        assert refine == {path: 2}

    def test_state_roundtrip_per_head(self):
        space = _space()
        on = dataclasses.replace(_EXACT, per_head_gphp=True)
        ms = MetricSet(list(_CONSTRAINED))

        def mk():
            return _seeded_store(space, 8, metrics=ms)

        s1 = BOSuggester(space, on, seed=5, store=mk())
        s1.suggest_batch(1)
        state = json.loads(json.dumps(s1.state_dict()))
        a = s1.suggest_batch(1)

        s2 = BOSuggester(space, on, seed=5, store=mk())
        s2.suggest_batch(1)
        s2.load_state_dict(state)
        assert s2.suggest_batch(1) == a

    def test_rebuild_after_drop_factors(self):
        """Per-head factors are X-only: eviction rebuilds them RNG-free and
        the next suggestion is unchanged."""
        space = _space()
        on = dataclasses.replace(_EXACT, per_head_gphp=True)
        ms = MetricSet(list(_CONSTRAINED))

        def run(drop):
            store = _seeded_store(space, 8, metrics=ms)
            sug = BOSuggester(space, on, seed=5, store=store)
            out = []
            for _ in range(3):
                c = sug.suggest_batch(1)[0]
                out.append(c)
                store.push_metrics(c, {"loss": _obj(c), "lat": c["x"] + c["y"]})
                if drop:
                    sug.cache.drop_factors()
            return out

        assert run(drop=False) == run(drop=True)
