"""Cost-aware decisions (PR 9): the EIpu math, the fused kernel's ``cost``
mode, budget-ledger semantics, the wire protocol's typed budget refusal,
and the cost-off bit-identity guarantee.

Parity idiom follows ``test_acq_score.py``: the Pallas kernel (interpret)
is triangulated against the standalone jnp oracle
(``acq_score_multi_ref``) and the xla composition; the property tests ride
``_hypothesis_compat`` so they degrade to skips where hypothesis is not
installed.
"""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from repro.core import (
    BOConfig,
    BOSuggester,
    Continuous,
    ObservationStore,
    SearchSpace,
    SelectionService,
    ServiceConfig,
    Tuner,
    TuningJobConfig,
)
from repro.core.budget import BudgetExhaustedError, BudgetLedger
from repro.core.blackbox import TabulatedBackend, deceptive_cheap_table
from repro.core.gp import gp as G
from repro.core.gp import params as P
from repro.core.gp.multi import solve_head_alphas
from repro.core.gp.slice_sampler import SliceSamplerConfig
from repro.core.history import bucket_size
from repro.core.optimize_acq import MultiMetricHead
from repro.core.rpc import (
    ErrorCode,
    ErrorReply,
    ObserveRequest,
    RegisterRequest,
    SuggestBatchRequest,
    bo_config_to_wire,
)
from repro.distributed.engine_client import RemoteService, _Connection
from repro.distributed.engine_server import EngineServer
from repro.kernels.acq_score.ops import acq_score, acq_score_multi
from repro.kernels.acq_score.ref import acq_score_multi_ref

TINY_SLICE = SliceSamplerConfig(num_samples=4, burn_in=2, thin=1)
ATOL = 1e-5


def _space():
    return SearchSpace([
        Continuous("x", 0.0, 1.0),
        Continuous("y", 0.0, 1.0),
    ])


def _cfg(cost_aware=False, **kw):
    return BOConfig(
        num_init=3,
        slice_config=TINY_SLICE,
        refit_every=3,
        incremental=True,
        cost_aware=cost_aware,
        **kw,
    )


# ------------------------------------------------------------------- ledger


class TestBudgetLedger:
    def test_charge_accumulates_and_reports(self):
        led = BudgetLedger(10.0)
        assert led.charge(3.0) == 3.0
        assert led.charge(4.5) == 7.5
        assert not led.exhausted
        assert led.remaining == pytest.approx(2.5)
        led.charge(2.5)
        assert led.exhausted
        assert led.remaining == 0.0

    def test_uncapped_tracks_but_never_exhausts(self):
        led = BudgetLedger(None)
        led.charge(1e9)
        assert not led.exhausted
        assert led.remaining == math.inf
        led.check("job")  # no raise

    def test_bad_charges_ignored(self):
        led = BudgetLedger(5.0)
        for bad in (-1.0, 0.0, float("nan"), float("inf")):
            led.charge(bad)
        assert led.spent == 0.0

    def test_check_raises_typed(self):
        led = BudgetLedger(1.0)
        led.charge(2.0)
        with pytest.raises(BudgetExhaustedError) as ei:
            led.check("myjob")
        assert "myjob" in str(ei.value)
        assert ei.value.spent == 2.0
        assert ei.value.max_cost == 1.0

    def test_snapshot_roundtrip(self):
        led = BudgetLedger(7.0)
        led.charge(2.25)
        snap = led.snapshot()
        fresh = BudgetLedger(None)
        fresh.load_snapshot(snap)
        assert fresh.max_cost == 7.0
        assert fresh.spent == 2.25
        assert fresh.snapshot() == snap


# ------------------------------------------------------- kernel "cost" mode


def _cost_posterior(seed, n, s, d):
    """Two-head posterior (objective + standardized log-cost) over random
    rows, mirroring what ``_decide_cost`` builds."""
    rng = np.random.default_rng(seed)
    nb = bucket_size(n)
    x = np.zeros((nb, d))
    x[:n] = rng.random((n, d))
    packed = np.stack([
        P.default_params(d).pack() + 0.1 * rng.standard_normal(3 * d + 2)
        for _ in range(s)
    ])
    params = P.GPHyperParams.unpack(jnp.asarray(packed), d)
    mask = np.zeros(nb, bool)
    mask[:n] = True
    y0 = np.zeros(nb)
    y0[:n] = rng.standard_normal(n)
    post = G.fit_posterior_batch(
        jnp.asarray(x), jnp.asarray(y0), params, jnp.asarray(mask),
        with_inverse=True,
    )
    zc = np.zeros(nb)
    zc[:n] = rng.standard_normal(n)
    yh = np.stack([y0, zc])
    alphas = solve_head_alphas(post, jnp.asarray(yh))
    return post, alphas, float(y0[:n].min()), rng


def _cost_head(alphas, y_best, eta):
    return MultiMetricHead(
        alphas=alphas,
        t_std=jnp.zeros((0,)),
        y_best=jnp.asarray(y_best),
        has_feasible=jnp.asarray(True),
        weights=jnp.asarray([[eta]]),
        y_best_w=jnp.zeros((1,)),
        head_posts=(),
    )


@pytest.mark.pallas
@pytest.mark.parametrize("n", [6, 40])
@pytest.mark.parametrize("s", [1, 8])
@pytest.mark.parametrize("d", [2, 12])
def test_cost_mode_kernel_parity(n, s, d):
    """pallas vs ref vs xla on mode="cost" (acceptance 1e-5; measured
    ~1e-8 in f64 interpret mode)."""
    post, alphas, y_best, rng = _cost_posterior(11 * n + s + d, n, s, d)
    xs = jnp.asarray(rng.random((300, d)))
    head = _cost_head(alphas, y_best, eta=1.7)
    ref = acq_score_multi_ref(
        post, alphas, xs, mode="cost", y_best=head.y_best,
        weights=head.weights,
    )
    got_x = acq_score_multi(post, head, xs, mode="cost", backend="xla")
    got_p = acq_score_multi(post, head, xs, mode="cost", backend="pallas")
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(ref), atol=ATOL)
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(got_x), atol=ATOL)


@pytest.mark.pallas
def test_cost_mode_eta_zero_is_plain_ei():
    """η = 0 turns the discount off exactly: cost-mode score == the fused
    single-head EI on the objective alpha."""
    post, alphas, y_best, rng = _cost_posterior(5, 24, 4, 3)
    xs = jnp.asarray(rng.random((128, 3)))
    head = _cost_head(alphas, y_best, eta=0.0)
    got = acq_score_multi(post, head, xs, mode="cost", backend="pallas")
    plain = acq_score(post, xs, jnp.asarray(y_best), acq="ei", backend="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain), atol=ATOL)


@pytest.mark.pallas
def test_cost_mode_zero_cost_alpha_is_plain_ei_exact():
    """The uniform-costs identity at the score level: zero log-cost targets
    give a zero cost alpha, so EIpu == EI *exactly*, any η."""
    post, alphas, y_best, rng = _cost_posterior(9, 30, 4, 2)
    zeroed = alphas.at[:, 1, :].set(0.0)
    xs = jnp.asarray(rng.random((200, 2)))
    a = acq_score_multi(
        post, _cost_head(zeroed, y_best, eta=3.0), xs, mode="cost",
        backend="pallas",
    )
    b = acq_score_multi(
        post, _cost_head(zeroed, y_best, eta=0.0), xs, mode="cost",
        backend="pallas",
    )
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -------------------------------------------------------------- properties

if HAVE_HYPOTHESIS:
    _etas = st.floats(min_value=0.0, max_value=5.0,
                      allow_nan=False, allow_infinity=False)
    _costs = st.floats(min_value=1e-3, max_value=1e3,
                       allow_nan=False, allow_infinity=False)
else:  # pragma: no cover - stub strategies, tests skip
    _etas = _costs = None


@pytest.mark.pallas
@settings(max_examples=10, deadline=None)
@given(eta=_etas, seed=st.integers(min_value=0, max_value=10))
def test_property_discount_monotone_in_predicted_cost(eta, seed):
    """At fixed EI, EIpu is non-increasing in the predicted cost: the
    discount factorizes as exp(−η·ẑc), so ordering anchors by ẑc
    (recovered from the η=1 score ratio) must order the η-score ratio
    the other way."""
    post, alphas, y_best, rng = _cost_posterior(seed, 20, 2, 2)
    xs = jnp.asarray(rng.random((64, 2)))

    def score(e):
        out = acq_score_multi_ref(
            post, alphas, xs, mode="cost", y_best=jnp.asarray(y_best),
            weights=jnp.asarray([[e]]),
        )
        # per (sample, anchor) element: the discount factorizes per GPHP
        # draw, not for the integrated score.
        return np.asarray(out).ravel()

    s0, s1, se = score(0.0), score(1.0), score(eta)
    keep = s0 > 1e-12  # EI ~ 0: the ratio is noise, skip those anchors
    zc = -np.log(s1[keep] / s0[keep])  # predicted standardized log-cost
    ratio = se[keep] / s0[keep]
    order = np.argsort(zc)
    assert np.all(np.diff(ratio[order]) <= 1e-9)
    np.testing.assert_allclose(ratio, np.exp(-eta * zc), rtol=1e-6)


@settings(max_examples=3, deadline=None)
@given(cost=_costs)
def test_property_eipu_equals_ei_under_uniform_costs(cost):
    """Uniform observed costs standardize to zero targets, so the
    cost-aware engine must pick (numerically) the same candidates as the
    cost-blind one — the ISSUE's EIpu == EI identity, at decision level."""
    space = _space()

    def build(cost_aware):
        store = ObservationStore(space)
        rng = np.random.default_rng(3)
        for c in space.sample(rng, 8):
            store.push(
                c, float((c["x"] - 0.4) ** 2 + (c["y"] - 0.6) ** 2),
                cost=cost if cost_aware else None,
            )
        return BOSuggester(
            space, _cfg(cost_aware=cost_aware, cost_cooling=2.0),
            seed=0, store=store,
        )

    got = build(True).suggest_batch(2)
    ref = build(False).suggest_batch(2)
    for ca, cb in zip(got, ref):
        assert ca.keys() == cb.keys()
        np.testing.assert_allclose(
            [ca[k] for k in sorted(ca)], [cb[k] for k in sorted(cb)],
            atol=1e-9,
        )


@settings(max_examples=10, deadline=None)
@given(max_cost=st.floats(min_value=2.0, max_value=40.0),
       seed=st.integers(min_value=0, max_value=20))
def test_property_overspend_bounded_by_inflight_trials(max_cost, seed):
    """Budgets gate new launches only: the ledger may overshoot max_cost
    by at most one in-flight trial per parallel slot, never more."""
    table = deceptive_cheap_table()

    class _Rand:
        def __init__(self):
            self._rng = np.random.default_rng(seed)

        def suggest_batch(self, k):
            return table.space.sample(self._rng, k)

    backend = TabulatedBackend(table, startup_cost=0.05)
    max_parallel = 2
    tuner = Tuner(
        table.space, table.objective, _Rand(), backend,
        TuningJobConfig(
            max_trials=60, max_parallel=max_parallel, seed=seed,
            job_name="budget-prop", max_cost=max_cost,
        ),
    )
    result = tuner.run()
    led = tuner.budget_ledger
    assert led is not None and led.exhausted
    worst_trial = max(
        table.total_cost(r) for r in range(table.num_configs)
    ) + 0.05
    assert led.spent <= max_cost + max_parallel * worst_trial
    assert len(result.trials) < 60  # the cap actually stopped the run


# --------------------------------------------------- budget over the wire


class TestBudgetWire:
    def test_server_side_refusal_code(self):
        """A raw connection that spends the budget gets the typed
        ``budget-exhausted`` refusal from the server on the next suggest."""
        space = _space()
        with EngineServer() as server:
            conn = _Connection(server.address, 5.0, 60.0)
            reply = conn.call(RegisterRequest(
                job_name="wirejob", space_spec=space.to_spec(), seed=5,
                bo_config=bo_config_to_wire(_cfg()), max_cost=1.0,
            ))
            assert not isinstance(reply, ErrorReply), reply
            lease = reply.lease
            reply = conn.call(ObserveRequest(
                job_name="wirejob", lease=lease, kind="charge", cost=2.0,
            ))
            assert not isinstance(reply, ErrorReply), reply
            reply = conn.call(SuggestBatchRequest(
                job_name="wirejob", lease=lease, k=1,
                store_version=0, num_pending=0,
            ))
            assert isinstance(reply, ErrorReply)
            assert reply.code == ErrorCode.BUDGET_EXHAUSTED
            conn.close()

    def test_client_raises_typed_error(self):
        """The RemoteService handle surfaces budget exhaustion as the same
        ``BudgetExhaustedError`` the in-process service raises."""
        space = _space()
        with EngineServer() as server:
            rsvc = RemoteService([server.address])
            rh = rsvc.register_job(
                "job", space, bo_config=_cfg(), seed=5, max_cost=1.0,
            )
            c = rh.suggest_batch(1)[0]
            rh.store.push(c, 0.5, cost=2.0)
            rh.observe_charge(2.0)
            with pytest.raises(BudgetExhaustedError):
                rh.suggest_batch(1)

    def test_in_process_handle_refuses_too(self):
        space = _space()
        svc = SelectionService(ServiceConfig())
        h = svc.register_job(
            "job", space, bo_config=_cfg(), seed=5, max_cost=1.0,
        )
        h.observe_charge(2.0)
        with pytest.raises(BudgetExhaustedError):
            h.suggest_batch(1)


# ----------------------------------------------------- cost-off identity


def _drive(handle, steps, with_costs, start=0):
    rng = np.random.default_rng(100 + start)
    stream = []
    for i in range(start, start + steps):
        c = handle.suggest_batch(1)[0]
        stream.append(c)
        handle.store.mark_pending(i, c)
        handle.store.clear_pending(i)
        y = float((c["x"] - 0.3) ** 2 + (c["y"] - 0.6) ** 2)
        handle.store.push(
            c, y, cost=float(1.0 + rng.random()) if with_costs else None
        )
    return stream


class TestCostOffIdentity:
    def test_recorded_costs_never_perturb_cost_blind_decisions(self):
        """With ``cost_aware=False``, pushed costs land in the store column
        and nothing else: the suggestion stream is bit-identical to a job
        that never saw a cost. (Two services: jobs sharing one service
        share pool state, which is its own — tested — feature.)"""
        space = _space()
        a = SelectionService(ServiceConfig()).register_job(
            "job", space, bo_config=_cfg(), seed=5)
        b = SelectionService(ServiceConfig()).register_job(
            "job", space, bo_config=_cfg(), seed=5)
        assert _drive(a, 8, True) == _drive(b, 8, False)

    def test_cost_off_snapshot_has_no_budget_keys(self):
        """Cost-off snapshots carry no ledger state and no cost column —
        v5 snapshots of cost-blind jobs are (content-wise) v4 snapshots."""
        space = _space()
        svc = SelectionService(ServiceConfig())
        h = svc.register_job("job", space, bo_config=_cfg(), seed=5)
        _drive(h, 6, False)
        snap = svc.snapshot_job("job")
        assert "budget" not in snap["suggester"]
        assert not any(snap["store"].get("own_costs") or [])

    def test_cost_off_socket_stream_identical(self):
        """Same guarantee across the wire: a remote cost-blind job fed
        costs walks the exact in-process no-cost stream."""
        space = _space()
        svc = SelectionService(ServiceConfig())
        h = svc.register_job("job", space, bo_config=_cfg(), seed=5)
        ref = _drive(h, 8, False)
        with EngineServer() as server:
            rsvc = RemoteService([server.address])
            rh = rsvc.register_job("job", space, bo_config=_cfg(), seed=5)
            got = _drive(rh, 8, True)
        assert got == ref


# --------------------------------------------------------- engine smoke


def test_cost_aware_engine_prefers_cheap_region():
    """End-to-end sanity: on the deceptive table the cost-aware engine
    spends materially less than a grid-uniform spend would suggest — the
    discount visibly steers sampling toward the cheap region."""
    table = deceptive_cheap_table()
    sugg = BOSuggester(
        table.space, _cfg(cost_aware=True, cost_cooling=2.0), seed=0
    )
    backend = TabulatedBackend(table, startup_cost=0.05)
    result = Tuner(
        table.space, table.objective, sugg, backend,
        TuningJobConfig(max_trials=15, max_parallel=2, seed=0,
                        job_name="steer"),
    ).run()
    grid_mean_cost = float(
        np.mean([table.total_cost(r) for r in range(table.num_configs)])
    ) + 0.05
    assert backend.now() < 15 * grid_mean_cost
    assert result.best_trial.objective < 0.5  # found *something*
