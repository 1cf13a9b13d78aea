"""Multi-metric decision engine: specs, store, acquisitions, engine modes,
workflow surface, wire protocol, and the M=1 bit-equivalence contract."""

import math

import numpy as np
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

import repro.core  # noqa: F401 — enables x64
import jax.numpy as jnp

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
    Tuner,
    TuningJobConfig,
    WarmStartPool,
    hypervolume,
    pareto_mask,
)
from repro.core.scheduler import SimBackend


def _space():
    return SearchSpace([Continuous("a", 0.0, 1.0), Continuous("b", 0.0, 1.0)])


CONSTRAINED = (
    MetricSpec("loss"),
    MetricSpec("lat", objective=False, threshold=0.9),
)
PARETO = (MetricSpec("loss"), MetricSpec("size"))


def _constrained_objective(cfg):
    loss = (cfg["a"] - 0.3) ** 2 + (cfg["b"] - 0.7) ** 2
    lat = cfg["a"] + cfg["b"]
    return [loss + 0.5 / (i + 1) for i in range(4)], 0.1, {
        "loss": loss, "lat": lat,
    }


def _pareto_objective(cfg):
    loss = (cfg["a"] - 0.2) ** 2 + 0.05 * cfg["b"]
    size = (cfg["b"] - 0.9) ** 2 + 0.05 * cfg["a"]
    return [loss], 0.1, {"loss": loss, "size": size}


# ---------------------------------------------------------------------------
# MetricSpec / MetricSet
# ---------------------------------------------------------------------------


def test_metric_spec_validation():
    with pytest.raises(ValueError):
        MetricSpec("m", goal="upward")
    with pytest.raises(ValueError):
        MetricSpec("m", threshold=1.0)  # objective with threshold
    with pytest.raises(ValueError):
        MetricSpec("m", objective=False)  # constraint without threshold
    assert MetricSpec("m", goal="maximize").sign == -1.0


def test_metric_set_ordering_and_modes():
    with pytest.raises(ValueError):
        MetricSet([])
    with pytest.raises(ValueError):  # first must be an objective
        MetricSet([MetricSpec("c", objective=False, threshold=1.0)])
    with pytest.raises(ValueError):  # objectives must precede constraints
        MetricSet([
            MetricSpec("o1"),
            MetricSpec("c", objective=False, threshold=1.0),
            MetricSpec("o2"),
        ])
    assert MetricSet([MetricSpec("o")]).mode == "single"
    assert MetricSet(list(CONSTRAINED)).mode == "constrained"
    assert MetricSet(list(PARETO)).mode == "pareto"


def test_metric_set_signing_and_feasibility():
    ms = MetricSet([
        MetricSpec("acc", goal="maximize"),
        MetricSpec("lat", objective=False, threshold=5.0),
    ])
    v = ms.signed_vector({"acc": 0.8, "lat": 3.0})
    assert v[0] == -0.8 and v[1] == 3.0
    assert ms.feasible({"acc": 0.8, "lat": 3.0})
    assert not ms.feasible({"acc": 0.8, "lat": 6.0})
    # maximize-constraint: feasible means >= threshold
    ms2 = MetricSet([
        MetricSpec("loss"),
        MetricSpec("acc", goal="maximize", objective=False, threshold=0.7),
    ])
    assert ms2.feasible({"loss": 1.0, "acc": 0.8})
    assert not ms2.feasible({"loss": 1.0, "acc": 0.6})
    assert ms2.signed_thresholds()[0] == -0.7


def test_feasible_missing_or_nonfinite_constraint_metric():
    """A metric dict missing a constraint metric (or carrying a non-finite
    one) is infeasible — never a crash (a misbehaving objective must not
    break ``Tuner.result``)."""
    ms = MetricSet(list(CONSTRAINED))
    assert ms.feasible({"loss": 1.0, "lat": 0.5})
    assert not ms.feasible({"loss": 1.0})
    assert not ms.feasible({"loss": 1.0, "lat": float("nan")})


def test_tuner_survives_broken_metric_dicts():
    """Objectives that drop metrics or return non-finite values: the job
    completes, broken rows never seed the GP, and the best trial is a
    fully-reported feasible one."""
    space = _space()
    calls = {"n": 0}

    def objective(cfg):
        calls["n"] += 1
        loss = (cfg["a"] - 0.3) ** 2
        lat = cfg["a"] + cfg["b"]
        if calls["n"] % 3 == 0:
            return [loss], 0.1, {"loss": float("nan"), "lat": lat}
        if calls["n"] % 5 == 0:
            return [loss], 0.1, {"loss": loss}  # constraint metric missing
        return [loss], 0.1, {"loss": loss, "lat": lat}

    jc = TuningJobConfig(max_trials=10, max_parallel=2, metrics=CONSTRAINED,
                         seed=1)
    t = Tuner(space, objective,
              BOSuggester(space, BOConfig(num_init=3).fast(), seed=1),
              SimBackend(), jc)
    res = t.run()
    assert all(tr.is_terminal for tr in res.trials)
    assert t.store.num_pending == 0
    assert np.all(np.isfinite(t.store.metric_matrix()))
    assert t.store.num_observations < len(res.trials)  # broken rows dropped
    ms = MetricSet(list(CONSTRAINED))
    assert ms.feasible(res.best_trial.metrics)
    for tr in res.pareto_front:
        assert ms.feasible(tr.metrics)


def test_metric_set_wire_roundtrip():
    ms = MetricSet(list(CONSTRAINED))
    back = MetricSet.from_wire(ms.to_wire())
    assert back.specs == ms.specs
    assert MetricSet.from_wire(None) is None


# ---------------------------------------------------------------------------
# ObservationStore Y block
# ---------------------------------------------------------------------------


def test_store_multimetric_push_and_standardize():
    space = _space()
    ms = MetricSet(list(CONSTRAINED))
    store = ObservationStore(space, metrics=ms)
    rng = np.random.default_rng(0)
    vals = []
    for cfg in space.sample(rng, 12):
        m = {"loss": rng.standard_normal(), "lat": rng.random()}
        assert store.push_metrics(cfg, m)
        vals.append([m["loss"], m["lat"]])
    vals = np.asarray(vals)
    assert store.num_metrics == 2
    assert np.allclose(store.metric_matrix(), vals)
    x, ystd, means, scales = store.standardized_metrics()
    # column 0 must be the exact single-metric standardization
    _, y0, m0, s0 = store.standardized()
    np.testing.assert_array_equal(ystd[:, 0], y0)
    assert means[0] == m0 and scales[0] == s0
    for j in range(2):
        assert abs(ystd[:, j].mean()) < 1e-12
        assert abs(ystd[:, j].std() - 1.0) < 1e-12
    # non-finite metric anywhere drops the whole row
    n = store.num_observations
    assert not store.push_metrics({"a": 0.1, "b": 0.2},
                                  {"loss": 1.0, "lat": float("nan")})
    assert store.num_observations == n
    # missing name raises
    with pytest.raises(KeyError):
        store.push_metrics({"a": 0.1, "b": 0.2}, {"loss": 1.0})
    # bare pushes are refused on multi stores
    with pytest.raises(ValueError):
        store.push({"a": 0.1, "b": 0.2}, 1.0)


def test_store_multimetric_snapshot_roundtrip():
    space = _space()
    ms = MetricSet(list(PARETO))
    store = ObservationStore(space, metrics=ms)
    rng = np.random.default_rng(1)
    for cfg in space.sample(rng, 7):
        store.push_metrics(cfg, {"loss": rng.random(), "size": rng.random()})
    store.mark_pending(3, {"a": 0.5, "b": 0.5})
    snap = store.snapshot()
    other = ObservationStore(space, metrics=ms)
    other.load_snapshot(snap)
    assert other.fingerprint() == store.fingerprint()
    np.testing.assert_array_equal(other.metric_matrix(), store.metric_matrix())
    # state_dict round trip too
    other2 = ObservationStore(space, metrics=ms)
    other2.load_state_dict(store.state_dict())
    np.testing.assert_array_equal(other2.metric_matrix(), store.metric_matrix())


def test_store_multimetric_refuses_warm_start():
    space = _space()
    pool = WarmStartPool()
    pool.add_parent([({"a": 0.1, "b": 0.2}, 1.0), ({"a": 0.3, "b": 0.4}, 2.0)])
    with pytest.raises(ValueError):
        ObservationStore(space, warm_start=pool,
                         metrics=MetricSet(list(PARETO)))


# ---------------------------------------------------------------------------
# Pareto utilities
# ---------------------------------------------------------------------------


def test_pareto_mask_basic():
    y = np.array([[1.0, 2.0], [2.0, 1.0], [2.0, 2.0], [0.5, 3.0]])
    np.testing.assert_array_equal(pareto_mask(y), [True, True, False, True])
    # duplicates of a front point are all kept
    y2 = np.array([[1.0, 1.0], [1.0, 1.0], [2.0, 0.5]])
    np.testing.assert_array_equal(pareto_mask(y2), [True, True, True])


def test_hypervolume_known_values():
    ref = np.array([2.0, 2.0])
    assert hypervolume(np.array([[1.0, 1.0]]), ref) == pytest.approx(1.0)
    # two staircase points
    y = np.array([[0.0, 1.0], [1.0, 0.0]])
    assert hypervolume(y, ref) == pytest.approx(2.0 + 1.0)
    # a dominated point adds nothing; a point outside ref adds nothing
    y3 = np.vstack([y, [[1.5, 1.5]], [[3.0, 0.0]]])
    assert hypervolume(y3, ref) == pytest.approx(3.0)
    # 3-D sanity: unit cube corner
    assert hypervolume(np.array([[0.0, 0.0, 0.0]]),
                       np.array([1.0, 1.0, 1.0])) == pytest.approx(1.0)


@settings(max_examples=40, deadline=None)
@given(
    st.lists(
        st.tuples(
            st.floats(0, 10, allow_nan=False, width=32),
            st.floats(0, 10, allow_nan=False, width=32),
        ),
        min_size=1,
        max_size=12,
    ),
    st.tuples(
        st.floats(0, 10, allow_nan=False, width=32),
        st.floats(0, 10, allow_nan=False, width=32),
    ),
)
def test_hypervolume_monotone_under_dominating_insert(points, newpoint):
    """Inserting a point that Pareto-dominates an existing one never
    decreases the dominated hypervolume."""
    y = np.asarray(points, dtype=np.float64)
    ref = y.max(axis=0) + 1.0
    base = hypervolume(y, ref)
    dominated_idx = 0
    dom = np.minimum(y[dominated_idx], np.asarray(newpoint))  # dominates row 0
    grown = hypervolume(np.vstack([y, dom[None, :]]), ref)
    assert grown >= base - 1e-9


# ---------------------------------------------------------------------------
# Constrained-EI properties
# ---------------------------------------------------------------------------


def _head_arrays(seed, m=16, s=3, c=2):
    rng = np.random.default_rng(seed)
    mu = rng.standard_normal((s, 1 + c, m))
    var = rng.random((s, m)) + 0.05
    return jnp.asarray(mu), jnp.asarray(var)


def test_feasibility_weight_bounds_and_no_constraint_degeneration():
    from repro.core.acquisition import expected_improvement
    from repro.core.multimetric import constrained_ei, feasibility_weight

    mu, var = _head_arrays(0)
    t = jnp.asarray([0.5, -0.2])
    w = feasibility_weight(mu[:, 1:, :], var, t)
    assert float(w.min()) >= 0.0 and float(w.max()) <= 1.0
    # no constraints: constrained EI equals plain EI on the objective head
    mu1 = mu[:, :1, :]
    vals = constrained_ei(mu1, var, jnp.asarray(-0.3), jnp.zeros((0,)),
                          jnp.asarray(True))
    plain = expected_improvement(mu1[:, 0, :], var, jnp.asarray(-0.3))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(plain), rtol=1e-12)


def test_constrained_ei_monotone_in_slack():
    """Raising a constraint threshold (more slack) never lowers the score."""
    from repro.core.multimetric import constrained_ei

    mu, var = _head_arrays(1, c=1)
    lo = constrained_ei(mu, var, jnp.asarray(0.0), jnp.asarray([-0.5]),
                        jnp.asarray(True))
    hi = constrained_ei(mu, var, jnp.asarray(0.0), jnp.asarray([0.5]),
                        jnp.asarray(True))
    assert np.all(np.asarray(hi) >= np.asarray(lo) - 1e-12)


if HAVE_HYPOTHESIS:

    @settings(max_examples=40, deadline=None)
    @given(
        st.floats(-3, 3, allow_nan=False),
        st.floats(0.05, 4.0, allow_nan=False),
        st.floats(-3, 3, allow_nan=False),
        st.floats(-2, 2, allow_nan=False),
        st.floats(0.0, 2.0, allow_nan=False),
    )
    def test_constrained_ei_properties(mu0, var0, muc, t, slack):
        """Weight ∈ [0,1]; score ≤ plain EI; monotone in constraint slack."""
        from repro.core.acquisition import expected_improvement
        from repro.core.multimetric import constrained_ei

        mu = jnp.asarray([[[mu0], [muc]]])  # (1, 2, 1)
        var = jnp.asarray([[var0]])
        ei = float(expected_improvement(jnp.asarray([[mu0]]), var,
                                        jnp.asarray(0.0))[0, 0])
        base = float(constrained_ei(mu, var, jnp.asarray(0.0),
                                    jnp.asarray([t]), jnp.asarray(True))[0, 0])
        more = float(constrained_ei(mu, var, jnp.asarray(0.0),
                                    jnp.asarray([t + slack]),
                                    jnp.asarray(True))[0, 0])
        assert 0.0 <= base <= ei + 1e-12
        assert more >= base - 1e-12


# ---------------------------------------------------------------------------
# engine modes
# ---------------------------------------------------------------------------


def _run_sim_tuner(metrics, objective, seed=0, max_trials=10, service=None,
                   job_name="job"):
    space = _space()
    jc = TuningJobConfig(max_trials=max_trials, max_parallel=2,
                         metrics=metrics, seed=seed, job_name=job_name)
    sugg = (None if service is not None
            else BOSuggester(space, BOConfig(num_init=3).fast(), seed=seed))
    t = Tuner(space, objective, sugg, SimBackend(), jc, service=service)
    return t.run()


def test_constrained_run_returns_best_feasible_and_front():
    res = _run_sim_tuner(CONSTRAINED, _constrained_objective, max_trials=12)
    ms = MetricSet(list(CONSTRAINED))
    completed = [t for t in res.trials
                 if t.state == "COMPLETED" and t.metrics is not None]
    assert len(completed) == 12
    # best is feasible
    assert res.best_trial.metrics["lat"] <= 0.9 + 1e-12
    # and is the minimum-loss feasible trial
    feas = [t for t in completed if ms.feasible(t.metrics)]
    assert res.best_trial.metrics["loss"] == min(
        t.metrics["loss"] for t in feas
    )
    # constrained mode: front is exactly the best feasible trial(s)
    assert [t.trial_id for t in res.pareto_front] == sorted(
        t.trial_id for t in feas
        if t.metrics["loss"] == res.best_trial.metrics["loss"]
    )


def test_pareto_front_is_exact_nondominated_set():
    res = _run_sim_tuner(PARETO, _pareto_objective, max_trials=12)
    completed = [t for t in res.trials
                 if t.state == "COMPLETED" and t.metrics is not None]
    y = np.asarray([[t.metrics["loss"], t.metrics["size"]] for t in completed])
    mask = pareto_mask(y)
    want = sorted(t.trial_id for t, keep in zip(completed, mask) if keep)
    got = [t.trial_id for t in res.pareto_front]
    assert got == want
    assert len(got) >= 1
    assert hypervolume(y[mask]) > 0.0


def test_multimetric_requires_ei():
    space = _space()
    ms = MetricSet(list(PARETO))
    store = ObservationStore(space, metrics=ms)
    rng = np.random.default_rng(0)
    for cfg in space.sample(rng, 5):
        store.push_metrics(cfg, {"loss": rng.random(), "size": rng.random()})
    from repro.core.optimize_acq import AcqOptConfig

    # rejected at bind time — before any cold-start trial spends budget
    with pytest.raises(ValueError):
        BOSuggester(space,
                    BOConfig(num_init=3, acq=AcqOptConfig(acq="lcb")).fast(),
                    seed=0, store=store)
    s = BOSuggester(space,
                    BOConfig(num_init=3, acq=AcqOptConfig(acq="lcb")).fast(),
                    seed=0)
    with pytest.raises(ValueError):
        s.bind_store(store)


def test_pareto_engine_state_roundtrip():
    """A restored engine redraws the exact scalarization weights (the numpy
    RNG is checkpointed), so mid-run restore continues the stream."""
    space = _space()
    ms = MetricSet(list(PARETO))

    def mk():
        store = ObservationStore(space, metrics=ms)
        rng = np.random.default_rng(3)
        for cfg in space.sample(rng, 6):
            store.push_metrics(cfg, {"loss": rng.random(), "size": rng.random()})
        return store

    s1 = BOSuggester(space, BOConfig(num_init=3).fast(), seed=5, store=mk())
    first = s1.suggest_batch(1)
    state = s1.state_dict()
    a = s1.suggest_batch(1)

    s2 = BOSuggester(space, BOConfig(num_init=3).fast(), seed=5, store=mk())
    s2.suggest_batch(1)  # advance to the same point
    s2.load_state_dict(state)
    b = s2.suggest_batch(1)
    assert a == b
    del first


# ---------------------------------------------------------------------------
# M=1 equivalence (acceptance: bit-identical to the pre-PR engine)
# ---------------------------------------------------------------------------


def _single_objective(cfg):
    # the curve ends exactly at the final objective, so the value-channel
    # completion (plain arm) and the metric-dict completion (declared arm)
    # resolve to the same final_objective — the equivalence must come from
    # the engine, not from convenient rounding.
    loss = (cfg["a"] - 0.4) ** 2 + (cfg["b"] - 0.6) ** 2
    curve = [loss + 0.3 / (i + 1) for i in range(4)] + [loss]
    return curve, 0.1, {"loss": loss}


def _single_objective_plain(cfg):
    values, costs, _ = _single_objective(cfg)
    return values, costs


def _table(res):
    return [(t.config, t.state, t.final_objective) for t in res.trials]


def test_m1_equivalence_in_process():
    plain = _run_sim_tuner(None, _single_objective_plain, max_trials=10)
    declared = _run_sim_tuner((MetricSpec("loss"),), _single_objective,
                              max_trials=10)
    assert _table(plain) == _table(declared)
    assert declared.pareto_front != []  # M=1 declared still tracks a front
    assert [t.trial_id for t in declared.pareto_front] == [
        plain.best_trial.trial_id
    ]


def test_m1_equivalence_over_socket():
    from repro.distributed.engine_client import RemoteService
    from repro.distributed.engine_server import EngineServer

    cfgbo = BOConfig(num_init=3).fast()
    plain = _run_sim_tuner(None, _single_objective_plain, max_trials=8)
    with EngineServer(
        service_config=ServiceConfig(default_bo_config=cfgbo)
    ) as server:
        svc = RemoteService([server.address])
        remote = _run_sim_tuner((MetricSpec("loss"),), _single_objective,
                                max_trials=8, service=svc, job_name="m1-eq")
        svc.job("m1-eq").close()
    assert _table(plain) == _table(remote)


def test_multimetric_socket_equivalence():
    """M=2 over the wire: remote trial table identical to in-process service
    mode (the multi-y observe path + metric specs survive the socket)."""
    from repro.distributed.engine_client import RemoteService
    from repro.distributed.engine_server import EngineServer

    cfgbo = BOConfig(num_init=3).fast()
    svc_local = SelectionService(ServiceConfig(default_bo_config=cfgbo))
    local = _run_sim_tuner(CONSTRAINED, _constrained_objective, max_trials=8,
                           service=svc_local, job_name="mm-eq")
    with EngineServer(
        service_config=ServiceConfig(default_bo_config=cfgbo)
    ) as server:
        svc = RemoteService([server.address])
        remote = _run_sim_tuner(CONSTRAINED, _constrained_objective,
                                max_trials=8, service=svc, job_name="mm-eq")
        svc.job("mm-eq").close()
    assert _table(local) == _table(remote)
    assert [t.metrics for t in local.trials] == [t.metrics for t in remote.trials]


def test_maximize_objective_ignores_raw_curve():
    """A maximize-goal metric: raw curve values carry the wrong sign, so the
    resolved dict value must drive ranking (not min() over the curve)."""
    space = _space()
    specs = (MetricSpec("reward", goal="maximize"),
             MetricSpec("lat", objective=False, threshold=1.9))

    def objective(cfg):
        reward = 10.0 * (1.0 - (cfg["a"] - 0.5) ** 2)
        # raw reward curve: minima of these are NOT the objective
        curve = [reward * f for f in (0.2, 0.6, 1.0)]
        return curve, 0.1, {"reward": reward, "lat": cfg["a"] + cfg["b"]}

    jc = TuningJobConfig(max_trials=8, max_parallel=2, metrics=specs, seed=2)
    t = Tuner(space, objective,
              BOSuggester(space, BOConfig(num_init=3).fast(), seed=2),
              SimBackend(), jc)
    res = t.run()
    ms = MetricSet(list(specs))
    feas = [tr for tr in res.trials
            if tr.state == "COMPLETED" and ms.feasible(tr.metrics)]
    assert feas
    # best = highest reward among feasible; objective = −reward exactly
    top = max(feas, key=lambda tr: tr.metrics["reward"])
    assert res.best_trial.trial_id == top.trial_id
    assert res.best_trial.objective == -top.metrics["reward"]


def test_stopped_maximize_trial_neither_seeds_nor_ranks():
    """An early-stopped maximize-goal trial has no metric dict; its raw
    curve (wrong sign) must not seed the signed GP store nor enter the
    best-trial pool."""
    space = _space()
    specs = (MetricSpec("reward", goal="maximize"),)

    class StopSecond:
        def should_stop(self, curve):
            return len(curve) >= 2

        def record_completed(self, curve):
            pass

    calls = {"n": 0}

    def objective(cfg):
        calls["n"] += 1
        reward = 5.0 + cfg["a"]
        if calls["n"] % 2 == 0:  # long curve: gets stopped at iteration 2
            return [reward * 0.1] * 6, 0.1, {"reward": reward}
        return [reward], 0.1, {"reward": reward}

    jc = TuningJobConfig(max_trials=8, max_parallel=1, metrics=specs, seed=4)
    t = Tuner(space, objective,
              BOSuggester(space, BOConfig(num_init=3).fast(), seed=4),
              SimBackend(), jc, stopping_rule=StopSecond())
    res = t.run()
    stopped = [tr for tr in res.trials if tr.state == "STOPPED"]
    completed = [tr for tr in res.trials if tr.state == "COMPLETED"]
    assert stopped and completed
    # store holds only signed completions (negative values, one per completed)
    assert t.store.num_observations == len(completed)
    assert np.all(t.store.metric_matrix()[:, 0] < 0)
    # best trial is a completed one, ranked by signed reward
    assert res.best_trial.state == "COMPLETED"
    assert res.best_trial.metrics["reward"] == max(
        tr.metrics["reward"] for tr in completed
    )
    # timeline never reports a wrong-signed (positive raw curve) best
    assert all(b < 0 for _, b in res.timeline if math.isfinite(b))


def test_thread_backend_streams_named_metrics():
    """ThreadBackend: a live objective returning a metric dict lands on the
    trial, drives feasibility, and seeds the multi-metric store."""
    from repro.core.scheduler import ThreadBackend

    space = _space()

    def live_objective(cfg, report):
        loss = (cfg["a"] - 0.3) ** 2 + (cfg["b"] - 0.7) ** 2
        for i in range(3):
            report(loss + 0.2 / (i + 1))
        return {"loss": loss, "lat": cfg["a"] + cfg["b"]}

    backend = ThreadBackend(max_workers=2)
    jc = TuningJobConfig(max_trials=6, max_parallel=2, metrics=CONSTRAINED)
    t = Tuner(space, live_objective,
              BOSuggester(space, BOConfig(num_init=3).fast(), seed=0),
              backend, jc)
    res = t.run()
    backend.shutdown()
    completed = [tr for tr in res.trials if tr.state == "COMPLETED"]
    assert len(completed) == 6
    assert all(set(tr.metrics) == {"loss", "lat"} for tr in completed)
    assert t.store.num_observations == 6
    assert res.best_trial.metrics["lat"] <= 0.9 + 1e-12


# ---------------------------------------------------------------------------
# engine snapshot with metrics (in-process restore)
# ---------------------------------------------------------------------------


def test_snapshot_restore_multimetric_continues_stream():
    cfgbo = BOConfig(num_init=3).fast()
    svc = SelectionService(ServiceConfig(default_bo_config=cfgbo))
    h = svc.register_job("mm", _space(), metrics=MetricSet(list(CONSTRAINED)))
    rng = np.random.default_rng(0)
    for cfg in _space().sample(rng, 6):
        h.observe_metrics(cfg, {"loss": rng.random(), "lat": rng.random()})
    snap = svc.snapshot_job("mm")
    svc2 = SelectionService(ServiceConfig(default_bo_config=cfgbo))
    h2 = svc2.restore_job(snap)
    assert h2.store.num_metrics == 2
    assert h.suggest_batch(2) == h2.suggest_batch(2)


# ---------------------------------------------------------------------------
# snapshot frame codecs (capability negotiation)
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# fused multi-head kernel parity (vs jnp oracle AND production composition)
# ---------------------------------------------------------------------------


def _multi_posterior(seed, n, s, d, m_heads):
    import jax
    from repro.core.gp import gp as gplib, params as gpparams
    from repro.core.gp.multi import solve_head_alphas
    from repro.core.history import bucket_size

    rng = np.random.default_rng(seed)
    nb = bucket_size(n)
    x = np.zeros((nb, d))
    x[:n] = rng.random((n, d))
    packed = np.stack([
        gpparams.default_params(d).pack()
        + 0.1 * rng.standard_normal(3 * d + 2)
        for _ in range(s)
    ])
    params = gpparams.GPHyperParams.unpack(jnp.asarray(packed), d)
    mask = np.zeros(nb, bool)
    mask[:n] = True
    y0 = np.zeros(nb)
    y0[:n] = rng.standard_normal(n)
    post = gplib.fit_posterior_batch(
        jnp.asarray(x), jnp.asarray(y0), params, jnp.asarray(mask),
        with_inverse=True,
    )
    yh = np.zeros((m_heads, nb))
    yh[0] = y0
    yh[1:, :n] = rng.standard_normal((m_heads - 1, n))
    alphas = solve_head_alphas(post, jnp.asarray(yh))
    return post, alphas, rng


@pytest.mark.pallas
@pytest.mark.slow
@pytest.mark.parametrize("n", [6, 40, 130])
@pytest.mark.parametrize("s", [1, 8])
@pytest.mark.parametrize("d", [2, 12])
@pytest.mark.parametrize("mode", ["constrained", "pareto", "rungs"])
def test_multi_head_kernel_parity_sweep(n, s, d, mode):
    """Fused multi-head scorer vs the standalone jnp oracle vs the
    production composition, across shape buckets / samples / dims / modes
    (acceptance bound 1e-5; measured ~1e-8 in f64 interpret mode)."""
    from repro.core.optimize_acq import MultiMetricHead
    from repro.kernels.acq_score.ops import acq_score_multi
    from repro.kernels.acq_score.ref import acq_score_multi_ref

    m_heads = 3
    post, alphas, rng = _multi_posterior(7 * n + s + d, n, s, d, m_heads)
    xs = jnp.asarray(rng.random((300, d)))
    if mode == "constrained":
        head = MultiMetricHead(
            alphas=alphas,
            t_std=jnp.asarray([0.4, -0.2]),
            y_best=jnp.asarray(-0.6),
            has_feasible=jnp.asarray(True),
            weights=jnp.zeros((0, 1)),
            y_best_w=jnp.zeros((0,)),
        )
        ref = acq_score_multi_ref(
            post, alphas, xs, mode=mode, t_std=head.t_std,
            y_best=head.y_best, has_feasible=True,
        )
    elif mode == "rungs":
        from repro.core.gp.per_resource import rung_head_weights

        weights = jnp.asarray(rung_head_weights([1, 3], m_heads - 1))
        head = MultiMetricHead(
            alphas=alphas,
            t_std=jnp.zeros((0,)),
            y_best=jnp.asarray(0.0),
            has_feasible=jnp.asarray(True),
            weights=weights,
            y_best_w=jnp.asarray(rng.standard_normal(m_heads)),
        )
        ref = acq_score_multi_ref(
            post, alphas, xs, mode=mode,
            weights=head.weights, y_best_w=head.y_best_w,
        )
    else:
        w = rng.random((8, 2)) + 1e-3
        w = w / w.sum(axis=1, keepdims=True)
        head = MultiMetricHead(
            alphas=alphas,
            t_std=jnp.asarray([0.4]),
            y_best=jnp.asarray(0.0),
            has_feasible=jnp.asarray(True),
            weights=jnp.asarray(w),
            y_best_w=jnp.asarray(rng.standard_normal(8)),
        )
        ref = acq_score_multi_ref(
            post, alphas, xs, mode=mode, t_std=head.t_std,
            weights=head.weights, y_best_w=head.y_best_w,
        )
    got_x = acq_score_multi(post, head, xs, mode=mode, backend="xla")
    got_p = acq_score_multi(post, head, xs, mode=mode, backend="pallas")
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(got_x), atol=1e-5)


@pytest.mark.pallas
def test_multi_engine_backend_invariance():
    """xla- and pallas-scored multi-metric engines walk identical
    suggestion streams (fit chain is backend-split, like the M=1 engine)."""
    space = _space()
    ms = MetricSet(list(CONSTRAINED))

    def run(backend):
        store = ObservationStore(space, metrics=ms)
        rng = np.random.default_rng(11)
        for cfg in space.sample(rng, 6):
            store.push_metrics(
                cfg, {"loss": rng.random(), "lat": rng.random()}
            )
        s = BOSuggester(space, BOConfig(num_init=3, backend=backend).fast(),
                        seed=2, store=store)
        out = []
        for _ in range(3):
            c = s.suggest_batch(1)[0]
            out.append(c)
            store.push_metrics(
                c, {"loss": (c["a"] - 0.3) ** 2, "lat": c["a"] + c["b"]}
            )
        return out

    a, b = run("xla"), run("pallas")
    for ca, cb in zip(a, b):
        for k in ca:
            assert abs(ca[k] - cb[k]) < 1e-6


def test_snapshot_frame_roundtrip_zlib():
    from repro.core.rpc import decode_snapshot_frame, encode_snapshot_frame

    snap = {"a": [1, 2, 3], "nested": {"x": "y" * 500}}
    frame = encode_snapshot_frame(snap, "zlib")
    assert decode_snapshot_frame(frame, "zlib") == snap
    with pytest.raises(ValueError):
        encode_snapshot_frame(snap, "lz77")


def test_snapshot_frame_zstd_gated():
    from repro.core import rpc

    if "zstd" in rpc.available_snapshot_codecs():
        snap = {"k": list(range(100))}
        frame = rpc.encode_snapshot_frame(snap, "zstd")
        assert rpc.decode_snapshot_frame(frame, "zstd") == snap
    else:
        with pytest.raises(ValueError):
            rpc.encode_snapshot_frame({}, "zstd")


def test_snapshot_codec_negotiation_over_socket():
    """A client that advertises codecs gets a compressed frame; one that
    advertises nothing gets plain JSON (old-client compatibility)."""
    from repro.core.rpc import (
        SnapshotRequest,
        available_snapshot_codecs,
        decode_snapshot_frame,
    )
    from repro.distributed.engine_client import RemoteService, _Connection
    from repro.distributed.engine_server import EngineServer

    cfgbo = BOConfig(num_init=2).fast()
    with EngineServer(
        service_config=ServiceConfig(default_bo_config=cfgbo)
    ) as server:
        svc = RemoteService([server.address])
        h = svc.register_job("codec-job", _space(), bo_config=cfgbo)
        h.store.push({"a": 0.2, "b": 0.3}, 1.0)
        # negotiated fetch (the client helper advertises its codecs)
        snap = h.fetch_snapshot()
        assert snap["job_name"] == "codec-job"
        # raw request with no codecs: plain JSON object comes back
        conn = _Connection(server.address, 5.0, 30.0)
        reply = conn.call(SnapshotRequest(job_name="codec-job",
                                          lease=h._lease))
        assert reply.codec is None
        assert reply.snapshot["job_name"] == "codec-job"
        # raw request advertising zlib: compressed frame comes back
        reply2 = conn.call(SnapshotRequest(job_name="codec-job",
                                           lease=h._lease,
                                           accept_codecs=["zlib"]))
        assert reply2.codec == "zlib"
        decoded = decode_snapshot_frame(reply2.snapshot["frame"], "zlib")
        assert decoded == reply.snapshot
        # server preference picks the best available codec
        best = available_snapshot_codecs()[0]
        reply3 = conn.call(SnapshotRequest(
            job_name="codec-job", lease=h._lease,
            accept_codecs=["zlib", "zstd"],
        ))
        assert reply3.codec == best
        conn.close()
        h.close()
