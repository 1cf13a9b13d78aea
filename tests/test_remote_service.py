"""Cross-process SelectionService: engine-snapshot round-trips (in-process,
fresh-subprocess), socket equivalence (same suggestion stream and trial table
as the in-process service, exact), replica-crash failover via lease expiry,
and the wire protocol's refusal paths (protocol/snapshot version mismatch,
expired/held leases, stale state)."""

import dataclasses
import json
import math
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.core import (
    BOConfig,
    Continuous,
    SearchSpace,
    SelectionService,
    ServiceConfig,
    Tuner,
    TuningJobConfig,
)
from repro.core.gp.slice_sampler import SliceSamplerConfig
from repro.core.rpc import (
    PROTOCOL_VERSION,
    ErrorCode,
    ErrorReply,
    ProtocolError,
    RegisterRequest,
    SuggestBatchRequest,
    bo_config_from_wire,
    bo_config_to_wire,
    decode_message,
    encode_message,
)
from repro.core.scheduler import SimBackend
from repro.core.service import PoolConflictError, SnapshotVersionError
from repro.distributed.engine_client import (
    RemoteService,
    RemoteServiceError,
    ReplicaDivergenceError,
    _Connection,
)
from repro.distributed.engine_server import EngineServer

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
    """suggest → pending → clear → push loop; returns the suggestion stream."""
    stream = []
    for i in range(start, start + steps):
        c = handle.suggest_batch(1)[0]
        stream.append(c)
        handle.store.mark_pending(i, c)
        handle.store.clear_pending(i)
        handle.store.push(c, _obj(c))
    return stream


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ---------------------------------------------------------------- snapshots


class TestSnapshotRoundTrip:
    def test_in_process_roundtrip_exact(self):
        """snapshot → restore into a fresh service → identical next-k."""
        space = _space()
        a = SelectionService(ServiceConfig())
        h = a.register_job("job", space, bo_config=_CFG, seed=5)
        _drive(h, 6)
        snap = a.snapshot_job("job")

        b = SelectionService(ServiceConfig())
        rh = b.restore_job(snap)
        assert rh.store.num_observations == h.store.num_observations
        assert _drive(h, 3, start=6) == _drive(rh, 3, start=6)

    def test_roundtrip_with_factors_exact(self):
        space = _space()
        a = SelectionService(ServiceConfig())
        h = a.register_job("job", space, bo_config=_CFG, seed=5)
        _drive(h, 6)
        snap = a.snapshot_job("job", include_factors=True)
        assert snap["cache"]["factors"] is not None

        rh = SelectionService(ServiceConfig()).restore_job(snap)
        assert _drive(h, 3, start=6) == _drive(rh, 3, start=6)

    def test_roundtrip_warm_start_folded(self):
        """A job that warm-started from a sibling snapshots/restores its
        parent rows exactly (no re-fold of the sibling's live history)."""
        space = _space()
        a = SelectionService(ServiceConfig(share_gphp=False))
        sib = a.register_job("sib", space, bo_config=_CFG, seed=0)
        _drive(sib, 5)
        h = a.register_job("job", space, bo_config=_CFG, seed=7)
        assert h.store.num_parents == 5
        _drive(h, 4)
        snap = a.snapshot_job("job")

        # the sibling keeps running on the source service: restore must not
        # see (or re-fold) those newer rows
        _drive(sib, 3, start=5)
        b = SelectionService(ServiceConfig(share_gphp=False))
        rh = b.restore_job(snap)
        assert rh.store.num_parents == 5
        assert _drive(h, 3, start=4) == _drive(rh, 3, start=4)

    def test_roundtrip_mid_fantasy_pending(self):
        """Snapshot taken with live pending candidates: the restored engine
        fantasizes over the same pending set and stays bit-identical."""
        space = _space()
        a = SelectionService(ServiceConfig())
        h = a.register_job("job", space, bo_config=_CFG, seed=5)
        _drive(h, 5)
        for j, c in enumerate(h.suggest_batch(2)):
            h.store.mark_pending(f"p{j}", c)
        snap = a.snapshot_job("job")

        rh = SelectionService(ServiceConfig()).restore_job(snap)
        assert rh.store.num_pending == 2
        assert h.suggest_batch(2) == rh.suggest_batch(2)

    def test_snapshot_is_json_safe(self):
        space = _space()
        a = SelectionService(ServiceConfig())
        h = a.register_job("job", space, bo_config=_CFG, seed=5)
        _drive(h, 4)
        snap = a.snapshot_job("job")
        rt = json.loads(json.dumps(snap))
        rh = SelectionService(ServiceConfig()).restore_job(rt)
        assert _drive(h, 2, start=4) == _drive(rh, 2, start=4)

    @pytest.mark.slow
    def test_restore_in_fresh_subprocess_exact(self, tmp_path):
        """The real cross-process claim: a *fresh interpreter* given nothing
        but the snapshot bytes continues the suggestion stream bit-exactly."""
        space = _space()
        a = SelectionService(ServiceConfig())
        h = a.register_job("job", space, bo_config=_CFG, seed=5)
        _drive(h, 6)
        snap_path = tmp_path / "snap.json"
        snap_path.write_text(json.dumps(a.snapshot_job("job")))
        expected = _drive(h, 3, start=6)

        child = (
            "import json, sys\n"
            "from repro.core.service import SelectionService, ServiceConfig\n"
            "snap = json.load(open(sys.argv[1]))\n"
            "h = SelectionService(ServiceConfig()).restore_job(snap)\n"
            "out = []\n"
            "def obj(c): return float((c['x']-0.3)**2 + (c['y']-0.1)**2)\n"
            "for i in range(6, 9):\n"
            "    c = h.suggest_batch(1)[0]\n"
            "    out.append(c)\n"
            "    h.store.mark_pending(i, c)\n"
            "    h.store.clear_pending(i)\n"
            "    h.store.push(c, obj(c))\n"
            "print(json.dumps(out))\n"
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"
        )
        proc = subprocess.run(
            [sys.executable, "-c", child, str(snap_path)],
            capture_output=True, text=True, env=env, timeout=600,
        )
        assert proc.returncode == 0, proc.stderr
        got = json.loads(proc.stdout.strip().splitlines()[-1])
        assert got == expected

    def test_version_mismatch_refused(self):
        space = _space()
        a = SelectionService(ServiceConfig())
        a.register_job("job", space, bo_config=_CFG, seed=5)
        snap = a.snapshot_job("job")
        snap["snapshot_version"] = 999
        with pytest.raises(SnapshotVersionError):
            SelectionService(ServiceConfig()).restore_job(snap)

    def test_pool_conflict_refused(self):
        """A service whose resident group pool diverged from the snapshot's
        refuses adoption instead of splicing the job onto foreign draws."""
        space = _space()
        a = SelectionService(ServiceConfig())
        h = a.register_job("job", space, bo_config=_CFG, seed=5)
        _drive(h, 6)  # past num_init + refit_every: pool has published draws
        snap = a.snapshot_job("job")
        assert snap["pool"]["samples"] is not None

        b = SelectionService(ServiceConfig())
        other = b.register_job("other", space, bo_config=_CFG, seed=11)
        _drive(other, 6)  # b's pool now holds different draws
        with pytest.raises(PoolConflictError):
            b.restore_job(snap)


class TestConfigWire:
    def test_bo_config_roundtrip(self):
        for cfg in (_CFG, dataclasses.replace(_CFG, fit_on_host=False)):
            blob = json.loads(json.dumps(bo_config_to_wire(cfg)))
            assert bo_config_from_wire(blob) == cfg


# ------------------------------------------------------------------- socket


class TestSocketEquivalence:
    def test_suggestion_stream_exact(self):
        space = _space()
        svc = SelectionService(ServiceConfig())
        h = svc.register_job("job", space, bo_config=_CFG, seed=5)
        ref = _drive(h, 8)

        with EngineServer() as server:
            rsvc = RemoteService([server.address])
            rh = rsvc.register_job("job", space, bo_config=_CFG, seed=5)
            got = _drive(rh, 8)
        assert got == ref

    @pytest.mark.slow
    def test_tuner_trial_table_exact(self):
        """Acceptance bar: a Tuner served by engine_server over a socket
        produces the same trial table and suggestion sequence as one served
        by the in-process SelectionService — exact, not tolerance-based."""
        ref = self._run_tuner(SelectionService(ServiceConfig(default_bo_config=_CFG)))
        with EngineServer(
            service_config=ServiceConfig(default_bo_config=_CFG)
        ) as server:
            got = self._run_tuner(RemoteService([server.address]))
        assert self._table(got) == self._table(ref)

    @pytest.mark.slow
    def test_replica_crash_failover_exact_no_retry_budget(self):
        """Kill the serving replica mid-job: the handle re-adopts onto the
        surviving replica from its last snapshot and the run completes with
        the *same trial table* — and replica death consumes no trial retry
        budget (it is infrastructure failure, not objective failure)."""
        ref = self._run_tuner(SelectionService(ServiceConfig(default_bo_config=_CFG)))

        s1 = EngineServer(service_config=ServiceConfig(default_bo_config=_CFG)).start()
        s2 = EngineServer(service_config=ServiceConfig(default_bo_config=_CFG)).start()
        killed = []

        def kill_after_third(tuner, trial):
            done = sum(1 for t in tuner.trials.values() if t.is_terminal)
            if done == 3 and not killed:
                s1.shutdown()
                killed.append(True)

        try:
            got = self._run_tuner(
                RemoteService([s1.address, s2.address], snapshot_every=4),
                callbacks=[kill_after_third],
            )
        finally:
            s2.shutdown()
        assert killed, "kill callback never fired"
        assert self._table(got) == self._table(ref)
        assert got.num_failed_attempts == ref.num_failed_attempts
        assert all(t.attempts == 1 for t in got.trials)

    @pytest.mark.slow
    def test_tuner_checkpoint_kill_restore(self, tmp_path):
        """Tuner checkpoint/restore works across the wire: a remote-mode job
        killed after its 3rd terminal trial and restored (a *new* Tuner
        re-registering via lease takeover, replaying the store into the
        replica, installing the checkpointed engine state) finishes with the
        same trial table as an uninterrupted in-process run."""
        ref = self._run_tuner(SelectionService(ServiceConfig(default_bo_config=_CFG)))

        class _Crash(Exception):
            pass

        def boom(tuner, trial):
            if sum(1 for t in tuner.trials.values() if t.is_terminal) == 3:
                raise _Crash()

        path = str(tmp_path / "remote_tuner.json")
        with EngineServer(
            service_config=ServiceConfig(default_bo_config=_CFG)
        ) as server:
            rsvc = RemoteService([server.address])
            with pytest.raises(_Crash):
                self._run_tuner(rsvc, callbacks=[boom], checkpoint_path=path)
            tuner = self._make_tuner(rsvc, checkpoint_path=path)
            tuner.restore()
            got = tuner.run()
        assert self._table(got) == self._table(ref)

    @classmethod
    def _run_tuner(cls, service, callbacks=(), checkpoint_path=None):
        return cls._make_tuner(service, callbacks, checkpoint_path).run()

    @staticmethod
    def _make_tuner(service, callbacks=(), checkpoint_path=None):
        space = _space()

        def objective(cfg):
            return _obj(cfg) + 0.5 * np.exp(-0.4 * np.arange(1, 6)), 1.0

        return Tuner(
            space, objective, None, SimBackend(startup_cost=2.0),
            TuningJobConfig(max_trials=8, max_parallel=2, job_name="job",
                            seed=3, checkpoint_path=checkpoint_path),
            service=service, callbacks=callbacks,
        )

    @staticmethod
    def _table(result):
        return [
            (t.trial_id, t.config, str(t.state), t.objective, t.attempts)
            for t in result.trials
        ]


class TestBudgetFailover:
    """PR 9: the budget ledger survives replica death. The client mirror
    re-charges the restored replica during oplog replay, so after a
    mid-spend SIGKILL-style failover the run completes with the same trial
    table and every ledger — mirror, surviving replica, in-process
    reference — agreeing on the spend."""

    _CA_CFG = BOConfig(
        num_init=3,
        slice_config=SliceSamplerConfig(num_samples=4, burn_in=2, thin=1),
        refit_every=3,
        incremental=True,
        cost_aware=True,
        cost_cooling=1.5,
    )

    @classmethod
    def _make(cls, service, callbacks=()):
        def objective(cfg):
            # config-dependent cost: the ledger totals differ run-shape by
            # run-shape, so agreement below is not vacuous
            return (_obj(cfg) + 0.5 * np.exp(-0.4 * np.arange(1, 6)),
                    0.5 + cfg["x"])

        return Tuner(
            _space(), objective, None, SimBackend(startup_cost=2.0),
            TuningJobConfig(max_trials=8, max_parallel=2, job_name="job",
                            seed=3, max_cost=500.0),
            service=service, callbacks=callbacks,
        )

    @pytest.mark.slow
    def test_replica_kill_mid_spend_ledger_and_table_agree(self):
        ref_tuner = self._make(
            SelectionService(ServiceConfig(default_bo_config=self._CA_CFG)))
        ref = ref_tuner.run()
        assert ref_tuner.budget_ledger.spent > 0.0

        sc = ServiceConfig(default_bo_config=self._CA_CFG)
        s1 = EngineServer(service_config=sc).start()
        s2 = EngineServer(service_config=sc).start()
        killed = []

        def kill_after_third(tuner, trial):
            done = sum(1 for t in tuner.trials.values() if t.is_terminal)
            if done == 3 and not killed:
                assert tuner.budget_ledger.spent > 0.0  # mid-spend
                # SIGKILL semantics: stop the listener AND sever the live
                # connection (daemon handler threads outlive shutdown())
                s1.shutdown()
                conn = tuner._service_handle._conn
                if conn is not None:
                    conn.close()
                killed.append(True)

        try:
            tuner = self._make(
                RemoteService([s1.address, s2.address], snapshot_every=4),
                callbacks=[kill_after_third],
            )
            got = tuner.run()
            replica_led = s2.service.job("job").budget_ledger
        finally:
            s2.shutdown()
        assert killed, "kill callback never fired"
        table = TestSocketEquivalence._table
        assert table(got) == table(ref)
        # three-way ledger agreement: client mirror == surviving replica
        # (re-charged via oplog replay) == uninterrupted in-process run
        mirror = tuner.budget_ledger
        assert mirror is not None and replica_led is not None
        assert mirror.spent == pytest.approx(replica_led.spent, abs=1e-9)
        assert mirror.spent == pytest.approx(
            ref_tuner.budget_ledger.spent, abs=1e-9)
        assert mirror.max_cost == replica_led.max_cost == 500.0


class TestLeases:
    def _register(self, conn, name="job", **kw):
        reply = conn.call(RegisterRequest(
            job_name=name, space_spec=_space().to_spec(), seed=5,
            bo_config=bo_config_to_wire(_CFG), **kw,
        ))
        assert not isinstance(reply, ErrorReply), reply
        return reply

    def test_expired_lease_refused_then_adoptable(self):
        clock = _FakeClock()
        with EngineServer(lease_ttl=30.0, clock=clock) as server:
            conn = _Connection(server.address, 5.0, 60.0)
            lease = self._register(conn).lease

            # live lease: a foreign register is refused
            conn2 = _Connection(server.address, 5.0, 60.0)
            reply = conn2.call(RegisterRequest(
                job_name="job", space_spec=_space().to_spec(), seed=5,
                bo_config=bo_config_to_wire(_CFG),
            ))
            assert isinstance(reply, ErrorReply)
            assert reply.code == ErrorCode.LEASE_HELD

            # TTL elapses: the old token is refused loudly...
            clock.t += 31.0
            reply = conn.call(SuggestBatchRequest(
                job_name="job", lease=lease, k=1,
                store_version=0, num_pending=0,
            ))
            assert isinstance(reply, ErrorReply)
            assert reply.code == ErrorCode.LEASE_EXPIRED

            # ...and the job is now adoptable by the other client
            self._register(conn2)
            conn.close()
            conn2.close()

    def test_request_renews_lease(self):
        clock = _FakeClock()
        with EngineServer(lease_ttl=30.0, clock=clock) as server:
            conn = _Connection(server.address, 5.0, 60.0)
            lease = self._register(conn).lease
            for _ in range(3):  # 3 × 20s idle, each renewed in between
                clock.t += 20.0
                reply = conn.call(SuggestBatchRequest(
                    job_name="job", lease=lease, k=1,
                    store_version=0, num_pending=0,
                ))
                assert not isinstance(reply, ErrorReply), reply
            conn.close()

    def test_same_replica_readopt_with_stale_baseline(self):
        """Lease expiry on a replica that still hosts the job: the server
        grants the lease on the *resident* state (fingerprint-verified)
        instead of restoring the stale snapshot baseline — which would have
        refused with a pool conflict (the resident pool advanced past the
        baseline because of this very job's refits) and bricked a
        single-replica fleet."""
        clock = _FakeClock()
        with EngineServer(lease_ttl=30.0, clock=clock) as server:
            # snapshot_every high: the baseline snapshot stays at
            # registration time while refits publish fresher pool draws.
            rsvc = RemoteService([server.address], snapshot_every=1000)
            rh = rsvc.register_job("job", _space(), bo_config=_CFG, seed=5)
            first = _drive(rh, 6)  # past num_init + refit: pool published
            clock.t += 31.0
            cont = _drive(rh, 3, start=6)

        svc = SelectionService(ServiceConfig())
        h = svc.register_job("job", _space(), bo_config=_CFG, seed=5)
        assert _drive(h, 6) == first
        assert _drive(h, 3, start=6) == cont

    def test_auto_heartbeat_keeps_lease_alive_while_idle(self):
        """Trials longer than the lease TTL produce no RPC traffic; the
        handle's background renewer must keep the lease alive through the
        idle gap (no re-registration, stream unaffected)."""
        with EngineServer(lease_ttl=1.5) as server:
            rsvc = RemoteService([server.address])
            rh = rsvc.register_job("job", _space(), bo_config=_CFG, seed=5)
            first = _drive(rh, 2)
            time.sleep(3.5)  # > 2× TTL with no requests
            with server._lock:
                lease = server._leases["job"]
                assert lease.token == rh._lease  # renewed, never re-granted
            cont = _drive(rh, 2, start=2)

        svc = SelectionService(ServiceConfig())
        h = svc.register_job("job", _space(), bo_config=_CFG, seed=5)
        assert _drive(h, 2) == first
        assert _drive(h, 2, start=2) == cont

    def test_client_readopts_transparently_on_expiry(self):
        clock = _FakeClock()
        with EngineServer(lease_ttl=30.0, clock=clock) as server:
            rsvc = RemoteService([server.address])
            rh = rsvc.register_job("job", _space(), bo_config=_CFG, seed=5)
            first = _drive(rh, 4)
            clock.t += 31.0  # lease silently expires server-side
            # next request is refused, the handle re-adopts from its last
            # snapshot + oplog replay, and the stream continues bit-exactly
            cont = _drive(rh, 2, start=4)

        svc = SelectionService(ServiceConfig())
        h = svc.register_job("job", _space(), bo_config=_CFG, seed=5)
        assert _drive(h, 4) == first
        assert _drive(h, 2, start=4) == cont

    def test_lease_held_waits_out_dead_holder(self):
        """A fresh client registering a name whose holder crashed (heartbeats
        stopped, lease lingering) must wait out the remaining TTL and adopt —
        the Tuner checkpoint-restore-in-a-new-process path — instead of
        failing on the first lease-held refusal."""
        with EngineServer(lease_ttl=1.5) as server:
            a = RemoteService([server.address])
            ha = a.register_job("job", _space(), bo_config=_CFG, seed=5)
            _drive(ha, 2)
            ha.close()  # simulated crash: renewals stop, lease lingers

            t0 = time.monotonic()
            b = RemoteService([server.address])
            hb = b.register_job("job", _space(), bo_config=_CFG, seed=5)
            waited = time.monotonic() - t0
            assert waited < 10.0
            assert hb.suggest_batch(1)  # the adopted job serves

    def test_lease_held_by_live_holder_refused(self):
        """A live holder keeps renewing (auto-heartbeat): a second client
        waiting for the lease must eventually get a loud lease-held refusal,
        never steal the job."""
        with EngineServer(lease_ttl=1.5) as server:
            a = RemoteService([server.address])
            a.register_job("job", _space(), bo_config=_CFG, seed=5)
            b = RemoteService([server.address])
            with pytest.raises(ProtocolError, match="lease-held"):
                b.register_job("job", _space(), bo_config=_CFG, seed=5)

    def test_unknown_job_refused(self):
        with EngineServer() as server:
            conn = _Connection(server.address, 5.0, 60.0)
            reply = conn.call(SuggestBatchRequest(
                job_name="ghost", lease="x", k=1, store_version=0, num_pending=0,
            ))
            assert isinstance(reply, ErrorReply)
            assert reply.code == ErrorCode.UNKNOWN_JOB
            conn.close()

    def test_close_joins_heartbeat_thread(self):
        """close() must not leave the daemon renewer running: it is joined
        (bounded) before the connection is torn down, so no renewal can be
        in flight once close() returns."""
        with EngineServer(lease_ttl=1.0) as server:
            rsvc = RemoteService([server.address])
            rh = rsvc.register_job("job", _space(), bo_config=_CFG, seed=5)
            _drive(rh, 1)
            t = rh._heartbeat_thread
            assert t is not None and t.is_alive()
            rh.close()
            assert not t.is_alive()
            assert rh._closed

    def test_closed_handle_cannot_release(self):
        """A renewal that slips past the stop event (or any late RPC) must
        not re-register the job and leave a fresh lease behind after
        close() — the regression this pins is a heartbeat racing close and
        re-adopting a handle the user already shut down."""
        with EngineServer(lease_ttl=1.0) as server:
            rsvc = RemoteService([server.address])
            rh = rsvc.register_job("job", _space(), bo_config=_CFG, seed=5)
            _drive(rh, 1)
            with server._lock:
                token_before = server._leases["job"].token
            rh.close()
            with pytest.raises(RemoteServiceError, match="closed"):
                rh.heartbeat()  # the slipped renewal
            with pytest.raises(RemoteServiceError, match="closed"):
                rh.suggest_batch(1)
            # server side: the old lease merely runs out; no new token was
            # ever granted to the closed handle
            with server._lock:
                assert server._leases["job"].token == token_before

    def test_closed_handle_never_restarts_renewer(self):
        with EngineServer(lease_ttl=1.0) as server:
            rsvc = RemoteService([server.address])
            rh = rsvc.register_job("job", _space(), bo_config=_CFG, seed=5)
            rh.close()
            dead = rh._heartbeat_thread
            rh._start_heartbeats()
            assert rh._heartbeat_thread is dead  # no fresh thread after close


class TestProtocolRefusals:
    def test_protocol_version_mismatch(self):
        with EngineServer() as server:
            conn = _Connection(server.address, 5.0, 60.0)
            raw = json.dumps({
                "protocol": PROTOCOL_VERSION + 1,
                "type": "heartbeat",
                "body": {"job_name": "j", "lease": "x"},
            }) + "\n"
            conn._sock.sendall(raw.encode())
            reply = decode_message(conn._rfile.readline())
            assert isinstance(reply, ErrorReply)
            assert reply.code == ErrorCode.PROTOCOL_MISMATCH
            conn.close()

    def test_snapshot_version_mismatch_over_wire(self):
        space = _space()
        svc = SelectionService(ServiceConfig())
        svc.register_job("job", space, bo_config=_CFG, seed=5)
        snap = svc.snapshot_job("job")
        snap["snapshot_version"] = 999
        with EngineServer() as server:
            conn = _Connection(server.address, 5.0, 60.0)
            reply = conn.call(RegisterRequest(job_name="job", snapshot=snap))
            assert isinstance(reply, ErrorReply)
            assert reply.code == ErrorCode.SNAPSHOT_MISMATCH
            conn.close()

    def test_stale_store_refused(self):
        with EngineServer() as server:
            conn = _Connection(server.address, 5.0, 60.0)
            reply = conn.call(RegisterRequest(
                job_name="job", space_spec=_space().to_spec(), seed=5,
                bo_config=bo_config_to_wire(_CFG),
            ))
            stale = conn.call(SuggestBatchRequest(
                job_name="job", lease=reply.lease, k=1,
                store_version=7, num_pending=0,  # replica store is empty
            ))
            assert isinstance(stale, ErrorReply)
            assert stale.code == ErrorCode.STALE_STATE
            conn.close()

    def test_codec_roundtrip_and_bad_input(self):
        msg = SuggestBatchRequest(
            job_name="j", lease="t", k=2, store_version=3, num_pending=1
        )
        assert decode_message(encode_message(msg)) == msg
        with pytest.raises(ProtocolError):
            decode_message(b"not json\n")
        with pytest.raises(ProtocolError):
            decode_message(json.dumps(
                {"protocol": PROTOCOL_VERSION, "type": "nope", "body": {}}
            ))
        # a malformed *error* frame must still fail typed, not TypeError
        with pytest.raises(ProtocolError):
            decode_message(json.dumps({"type": "error", "body": {}}))

    def test_engine_state_rpc_matches_in_process(self):
        """RemoteSuggester.state_dict (the per-event Tuner checkpoint blob)
        travels as a dedicated constant-size RPC and equals the in-process
        engine's state exactly."""
        svc = SelectionService(ServiceConfig())
        h = svc.register_job("job", _space(), bo_config=_CFG, seed=5)
        _drive(h, 5)
        with EngineServer() as server:
            rsvc = RemoteService([server.address])
            rh = rsvc.register_job("job", _space(), bo_config=_CFG, seed=5)
            _drive(rh, 5)
            remote_state = rh.suggester.state_dict()
        local_state = json.loads(json.dumps(h.suggester.state_dict()))
        assert json.loads(json.dumps(remote_state)) == local_state

    def test_stale_handle_raises(self):
        with EngineServer() as server:
            rsvc = RemoteService([server.address])
            h1 = rsvc.register_job("job", _space(), bo_config=_CFG, seed=5)
            rsvc.register_job("job", _space(), bo_config=_CFG, seed=5)
            assert h1.stale
            with pytest.raises(RuntimeError, match="stale"):
                h1.suggest_batch(1)
