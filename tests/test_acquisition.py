"""Acquisition functions: closed-form EI vs Monte Carlo, optimizer behaviour,
and hypothesis property tests of the acquisition math (degrade to skips when
``hypothesis`` is unavailable — see ``_hypothesis_compat``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from repro.core.acquisition import (
    expected_improvement,
    integrate_over_samples,
    lcb,
    thompson_draws,
)
from repro.core.gp import gp as G
from repro.core.gp import params as P
from repro.core.optimize_acq import AcqOptConfig, optimize_acquisition
from repro.core.sobol import sobol_sample


def test_ei_matches_monte_carlo():
    mu = jnp.asarray([0.0, 1.0, -0.5])
    var = jnp.asarray([1.0, 0.25, 4.0])
    y_best = jnp.asarray(0.3)
    closed = expected_improvement(mu, var, y_best)
    rng = np.random.default_rng(0)
    draws = rng.standard_normal((400_000, 3)) * np.sqrt(np.asarray(var)) + np.asarray(mu)
    mc = np.maximum(0.0, float(y_best) - draws).mean(axis=0)
    np.testing.assert_allclose(np.asarray(closed), mc, atol=5e-3)


def test_ei_zero_when_certain_and_worse():
    # tiny variance, mean above y_best ⇒ no improvement possible
    ei = expected_improvement(jnp.asarray([5.0]), jnp.asarray([1e-12]), jnp.asarray(0.0))
    assert float(ei[0]) == pytest.approx(0.0, abs=1e-9)


def test_ei_increases_with_variance():
    y_best = jnp.asarray(0.0)
    mu = jnp.asarray([1.0, 1.0])
    var = jnp.asarray([0.01, 4.0])
    ei = expected_improvement(mu, var, y_best)
    assert float(ei[1]) > float(ei[0])


def test_lcb_orders_by_optimism():
    vals = lcb(jnp.asarray([0.0, 0.0]), jnp.asarray([1.0, 4.0]), kappa=2.0)
    assert float(vals[1]) > float(vals[0])


def test_thompson_draw_shapes():
    d = thompson_draws(jnp.zeros((3, 7)), jnp.ones((3, 7)), jax.random.PRNGKey(0))
    assert d.shape == (3, 7)


def _toy_posterior(n=16, d=2, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.random((n, d)))
    y = jnp.asarray(np.sin(5 * np.asarray(x[:, 0])))
    y = (y - y.mean()) / (y.std() + 1e-12)
    return G.fit_gp(x, y, P.default_params(d)), x, y


def test_optimize_acquisition_returns_sorted_valid_points():
    post, x, y = _toy_posterior()
    anchors = jnp.asarray(sobol_sample(2, 256))
    cands, vals = optimize_acquisition(
        post, anchors, jnp.asarray(float(jnp.min(y))),
        jnp.zeros((8, 2)), jnp.zeros(8, bool), jax.random.PRNGKey(0),
        AcqOptConfig(num_anchors=256),
    )
    assert cands.shape == (8, 2)
    assert bool(jnp.all((cands >= 0) & (cands <= 1)))
    v = np.asarray(vals)
    assert (np.diff(v) <= 1e-9).all()  # sorted desc


def test_pending_exclusion():
    post, x, y = _toy_posterior()
    anchors = jnp.asarray(sobol_sample(2, 256))
    cfg = AcqOptConfig(num_anchors=256, exclusion_radius=0.05)
    # first, find the unconstrained best candidate
    free, _ = optimize_acquisition(
        post, anchors, jnp.asarray(float(jnp.min(y))),
        jnp.zeros((8, 2)), jnp.zeros(8, bool), jax.random.PRNGKey(0), cfg,
    )
    top = free[0]
    # now mark it pending: the new best must be outside the exclusion ball
    pend = jnp.zeros((8, 2)).at[0].set(top)
    mask = jnp.zeros(8, bool).at[0].set(True)
    excl, _ = optimize_acquisition(
        post, anchors, jnp.asarray(float(jnp.min(y))),
        pend, mask, jax.random.PRNGKey(0), cfg,
    )
    dist = float(jnp.max(jnp.abs(excl[0] - top)))
    assert dist >= cfg.exclusion_radius - 1e-6


# ------------------------------------------------- property-based (hypothesis)
# Strategies draw RNG seeds; moments are generated with numpy so value ranges
# stay controlled (wide but finite mu/var/y_best in standardized space).
_SEEDS = st.integers(min_value=0, max_value=2**32 - 1) if HAVE_HYPOTHESIS else None


def _moments(seed, s=4, m=16):
    rng = np.random.default_rng(seed)
    mu = jnp.asarray(rng.uniform(-10.0, 10.0, (s, m)))
    var = jnp.asarray(10.0 ** rng.uniform(-12.0, 2.0, (s, m)))
    y_best = jnp.asarray(rng.uniform(-10.0, 10.0))
    return mu, var, y_best


@settings(max_examples=30, deadline=None)
@given(_SEEDS)
def test_property_ei_nonnegative(seed):
    mu, var, y_best = _moments(seed)
    ei = expected_improvement(mu, var, y_best)
    assert bool(jnp.all(ei >= 0.0))
    assert bool(jnp.all(jnp.isfinite(ei)))


@settings(max_examples=30, deadline=None)
@given(_SEEDS)
def test_property_ei_vanishes_as_sigma_to_zero_when_worse(seed):
    """σ → 0 with μ > y*: no improvement is possible, EI must → 0."""
    rng = np.random.default_rng(seed)
    y_best = jnp.asarray(rng.uniform(-5.0, 5.0))
    mu = y_best + jnp.asarray(rng.uniform(0.1, 10.0, 16))  # strictly worse
    for log_var in (-8.0, -10.0, -13.0):
        ei = expected_improvement(mu, jnp.asarray(10.0**log_var), y_best)
        assert float(jnp.max(ei)) < 1e-3 * 10 ** (log_var / 2 + 4)
    ei0 = expected_improvement(mu, jnp.zeros(16), y_best)
    assert float(jnp.max(ei0)) == pytest.approx(0.0, abs=1e-9)


@settings(max_examples=30, deadline=None)
@given(_SEEDS)
def test_property_lcb_monotone_in_kappa(seed):
    """Negated LCB (larger-is-better) must be non-decreasing in κ."""
    mu, var, _ = _moments(seed)
    kappas = sorted(np.random.default_rng(seed).uniform(0.0, 8.0, 4))
    prev = lcb(mu, var, kappas[0])
    for k in kappas[1:]:
        cur = lcb(mu, var, k)
        assert bool(jnp.all(cur >= prev - 1e-12))
        prev = cur


@settings(max_examples=30, deadline=None)
@given(_SEEDS)
def test_property_integrated_acq_invariant_to_sample_permutation(seed):
    """The GPHP integral (mean over S) must not care about sample order."""
    mu, var, y_best = _moments(seed, s=6, m=8)
    perm = np.random.default_rng(seed + 1).permutation(6)
    for vals in (expected_improvement(mu, var, y_best), lcb(mu, var, 2.0)):
        base = integrate_over_samples(vals)
        shuffled = integrate_over_samples(vals[perm])
        np.testing.assert_allclose(
            np.asarray(base), np.asarray(shuffled), rtol=1e-12, atol=1e-12
        )


@settings(max_examples=10, deadline=None)
@given(_SEEDS)
def test_property_fused_scores_invariant_to_posterior_permutation(seed):
    """Permuting the posterior's GPHP samples permutes per-sample scores and
    leaves the integrated acquisition unchanged — on the fused kernel too."""
    from repro.kernels.acq_score.ops import acq_score

    rng = np.random.default_rng(seed)
    n, d, S = 8, 2, 4
    x = jnp.asarray(rng.random((n, d)))
    y = jnp.asarray(rng.standard_normal(n))
    packed = jnp.stack(
        [P.default_params(d).pack() + 0.1 * rng.standard_normal(3 * d + 2)
         for _ in range(S)]
    )
    post = G.fit_posterior_batch(x, y, P.GPHyperParams.unpack(packed, d))
    perm = rng.permutation(S)
    shuffled = G.GPPosterior(
        x_train=post.x_train,
        mask=post.mask,
        chol=post.chol[perm],
        alpha=post.alpha[perm],
        params=jax.tree.map(lambda p: p[perm], post.params),
    )
    anchors = jnp.asarray(rng.random((32, d)))
    y_best = jnp.asarray(float(y.min()))
    for backend in ("xla", "pallas"):
        a = acq_score(post, anchors, y_best, backend=backend)
        b = acq_score(shuffled, anchors, y_best, backend=backend)
        np.testing.assert_allclose(np.asarray(a[perm]), np.asarray(b), atol=1e-12)
        np.testing.assert_allclose(
            np.asarray(integrate_over_samples(a)),
            np.asarray(integrate_over_samples(b)),
            atol=1e-12,
        )


def test_refinement_does_not_hurt():
    """Gradient refinement must return acquisition ≥ the best raw anchor."""
    post, x, y = _toy_posterior(seed=3)
    anchors = jnp.asarray(sobol_sample(2, 128))
    y_best = jnp.asarray(float(jnp.min(y)))
    cfg0 = AcqOptConfig(num_anchors=128, refine_steps=0)
    cfg1 = AcqOptConfig(num_anchors=128, refine_steps=30)
    _, v0 = optimize_acquisition(post, anchors, y_best, jnp.zeros((8, 2)),
                                 jnp.zeros(8, bool), jax.random.PRNGKey(1), cfg0)
    _, v1 = optimize_acquisition(post, anchors, y_best, jnp.zeros((8, 2)),
                                 jnp.zeros(8, bool), jax.random.PRNGKey(1), cfg1)
    assert float(v1[0]) >= float(v0[0]) - 1e-9


def _subjaxprs(eqn):
    """The jaxprs nested in one equation's params (calls, loop bodies)."""
    for val in eqn.params.values():
        for v in val if isinstance(val, (tuple, list)) else (val,):
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield inner


def _primitives(jaxpr, out):
    """Every primitive name in ``jaxpr``, nested calls and loop bodies too."""
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for inner in _subjaxprs(eqn):
            _primitives(inner, out)
    return out


def _stage_primitives(jaxpr, stage):
    """Primitives of the nested jitted call named ``stage``
    (``optimize_acq._stage``), searched through the whole program."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("pjit", "jit") and eqn.params["name"] == stage:
            return _primitives(eqn.params["jaxpr"].jaxpr, set())
        for inner in _subjaxprs(eqn):
            found = _stage_primitives(inner, stage)
            if found is not None:
                return found
    return None


@pytest.mark.parametrize("cached", [True, False], ids=["cached-inverse", "no-cache"])
def test_refine_solves_only_without_cached_inverse(cached):
    """The refinement stage reads the cached L⁻¹: its traced program holds no
    ``triangular_solve`` when the posterior carries L⁻¹, and still holds one
    when it does not (the fallback for posteriors without the cache)."""
    x = jnp.asarray(np.random.default_rng(0).random((8, 2)))
    y = jnp.asarray(np.sin(5 * np.asarray(x[:, 0])))
    batch = jax.tree.map(lambda a: jnp.stack([a, a]), P.default_params(2))
    post = G.fit_posterior_batch(x, y, batch, with_inverse=cached)
    cfg = AcqOptConfig(num_anchors=16, num_refine=2, refine_steps=2)
    jaxpr = jax.make_jaxpr(
        lambda post: optimize_acquisition(
            post, jnp.asarray(sobol_sample(2, 16)), jnp.min(y),
            jnp.zeros((4, 2)), jnp.zeros(4, bool), jax.random.PRNGKey(0), cfg,
        )
    )(post)
    refine = _stage_primitives(jaxpr.jaxpr, "refine")
    assert refine is not None and "scan" in refine
    assert ("triangular_solve" in refine) == (not cached)
    if cached:
        assert "dot_general" in refine
