"""Slice sampler: support constraints + statistical recovery of a known target."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gp import gp as G
from repro.core.gp import params as P
from repro.core.gp.fit import map_gphps, mcmc_gphps
from repro.core.gp.slice_sampler import SliceSamplerConfig, slice_sample_chain


def test_gaussian_target_moments():
    """Sampling a 3-d Gaussian recovers mean/std within MC error."""
    mean = jnp.asarray([1.0, -2.0, 0.5])
    std = jnp.asarray([0.5, 1.5, 1.0])

    def log_prob(z):
        return -0.5 * jnp.sum(((z - mean) / std) ** 2)

    cfg = SliceSamplerConfig(num_samples=900, burn_in=100, thin=2, step_size=1.0)
    samples = slice_sample_chain(log_prob, jnp.zeros(3), jax.random.PRNGKey(0), cfg)
    assert samples.shape == (400, 3)
    got_mean = np.asarray(jnp.mean(samples, axis=0))
    got_std = np.asarray(jnp.std(samples, axis=0))
    np.testing.assert_allclose(got_mean, np.asarray(mean), atol=0.25)
    np.testing.assert_allclose(got_std, np.asarray(std), rtol=0.35)


def test_respects_hard_support():
    """-inf outside a box must never be escaped."""

    def log_prob(z):
        inside = jnp.all(jnp.abs(z) < 1.0)
        return jnp.where(inside, -0.5 * jnp.sum(z * z), -jnp.inf)

    cfg = SliceSamplerConfig(num_samples=300, burn_in=50, thin=1, step_size=2.0)
    samples = slice_sample_chain(log_prob, jnp.zeros(2), jax.random.PRNGKey(1), cfg)
    assert bool(jnp.all(jnp.abs(samples) < 1.0))


def test_gphp_chain_stays_in_bounds_and_improves():
    rng = np.random.default_rng(0)
    n, d = 24, 2
    x = jnp.asarray(rng.random((n, d)))
    f = np.sin(6 * np.asarray(x[:, 0]))
    y = jnp.asarray((f - f.mean()) / f.std())
    mask = jnp.ones(n, bool)
    bounds = P.default_bounds(d)
    z0 = jnp.clip(P.default_params(d).pack(), bounds.lower + 1e-4, bounds.upper - 1e-4)
    cfg = SliceSamplerConfig(num_samples=80, burn_in=40, thin=4)
    samples = mcmc_gphps(x, y, mask, bounds, z0, jax.random.PRNGKey(0), cfg)
    assert samples.shape == (cfg.num_kept, P.GPHyperParams.packed_size(d))
    assert bool(jnp.all(samples >= bounds.lower - 1e-9))
    assert bool(jnp.all(samples <= bounds.upper + 1e-9))
    # the chain should find higher-posterior GPHPs than the init
    lp0 = G.log_posterior_density(x, y, z0, bounds, mask)
    lps = [G.log_posterior_density(x, y, s, bounds, mask) for s in samples]
    assert max(float(v) for v in lps) > float(lp0)


def test_map_beats_init():
    rng = np.random.default_rng(1)
    n, d = 20, 2
    x = jnp.asarray(rng.random((n, d)))
    f = np.cos(4 * np.asarray(x[:, 1]))
    y = jnp.asarray((f - f.mean()) / f.std())
    mask = jnp.ones(n, bool)
    bounds = P.default_bounds(d)
    z0 = jnp.clip(P.default_params(d).pack(), bounds.lower + 1e-4, bounds.upper - 1e-4)
    best = map_gphps(x, y, mask, bounds, z0, jax.random.PRNGKey(0))
    assert float(G.log_posterior_density(x, y, best, bounds, mask)) > float(
        G.log_posterior_density(x, y, z0, bounds, mask)
    )


def test_fit_runs_on_host_when_tpu_is_default(monkeypatch):
    """``BOConfig.fit_on_host`` (the default) runs the GPHP fit on the host
    CPU device whatever JAX's default backend is, and hands back the same
    bits as a fit on the default device, uncommitted, on the caller's
    default device. ``fit_backend="pallas"`` is refused on the host rather
    than interpreted."""
    import dataclasses

    import pytest

    from repro.core import BOConfig, BOSuggester, Continuous, SearchSpace
    from repro.core import suggest as S

    host = jax.devices("cpu")[0]
    placed = []

    def spy(fit):
        def run(*args):
            placed.append(jax.config.jax_default_device)
            return fit(*args)

        return run

    monkeypatch.setattr(S, "mcmc_gphps", spy(mcmc_gphps))
    monkeypatch.setattr(S, "map_gphps", spy(map_gphps))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    space = SearchSpace([Continuous("a", 0.0, 1.0), Continuous("b", 0.0, 1.0)])
    rng = np.random.default_rng(3)
    n = 12
    data = (jnp.asarray(rng.random((n, 2))), jnp.asarray(rng.standard_normal(n)),
            jnp.ones(n, bool))
    small = SliceSamplerConfig(num_samples=20, burn_in=10, thin=2)
    for method in ("mcmc", "map"):
        on_host = BOConfig(gphp_method=method, slice_config=small)
        assert on_host.fit_on_host
        on_dev = dataclasses.replace(on_host, fit_on_host=False)
        placed.clear()
        got = BOSuggester(space, on_host, seed=0)._fit_gphps(*data)
        want = BOSuggester(space, on_dev, seed=0)._fit_gphps(*data)
        assert placed == [host, None]
        assert not got.committed
        assert got.devices() == {jax.devices()[0]}
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError, match="fit_on_host"):
        BOConfig(fit_backend="pallas")
    assert BOConfig(fit_backend="pallas", fit_on_host=False).fit_backend == "pallas"
