"""GP core: MLL oracle, masking exactness, PSD property, warping, prediction."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # degrades to skips offline

from repro.core.gp import gp as G
from repro.core.gp import params as P
from repro.core.gp.kernels import matern52_ard
from repro.core.gp.warping import kumaraswamy_cdf, warp_inputs


def _data(n=20, d=3, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.random((n, d))
    f = np.sin(3 * x[:, 0]) + 0.5 * x[:, 1] ** 2 - x[:, 2]
    y = (f - f.mean()) / (f.std() + 1e-12)
    return jnp.asarray(x), jnp.asarray(y)


def test_mll_matches_numpy_oracle():
    x, y = _data()
    p = P.default_params(3)
    got = float(G.log_marginal_likelihood(x, y, p))
    k = np.array(matern52_ard(x, x, p))
    k = k + (np.exp(2 * float(p.log_noise)) + 1e-8) * np.eye(len(y))
    sign, logdet = np.linalg.slogdet(k)
    assert sign > 0
    quad = np.asarray(y) @ np.linalg.solve(k, np.asarray(y))
    want = -0.5 * (quad + logdet + len(y) * np.log(2 * np.pi))
    assert got == pytest.approx(want, rel=1e-9)


def test_mask_padding_is_exact():
    x, y = _data()
    p = P.default_params(3)
    base = float(G.log_marginal_likelihood(x, y, p))
    xp = jnp.concatenate([x, jnp.full((7, 3), 0.42)], axis=0)
    yp = jnp.concatenate([y, jnp.full((7,), 1e6)], axis=0)
    mask = jnp.concatenate([jnp.ones(len(y), bool), jnp.zeros(7, bool)])
    padded = float(G.log_marginal_likelihood(xp, yp, p, mask))
    assert padded == pytest.approx(base, abs=1e-9)
    # prediction also unaffected
    post_a = G.fit_gp(x, y, p)
    post_b = G.fit_gp(xp, yp, p, mask)
    xs = jnp.asarray(np.random.default_rng(1).random((5, 3)))
    mu_a, var_a = G.predict(post_a, xs)
    mu_b, var_b = G.predict(post_b, xs)
    np.testing.assert_allclose(mu_a, mu_b, atol=1e-9)
    np.testing.assert_allclose(var_a, var_b, atol=1e-9)


@settings(max_examples=25, deadline=None)
@given(
    st.integers(2, 16),
    st.integers(1, 4),
    st.integers(0, 2**31 - 1),
    st.floats(-1.5, 1.5),
)
def test_kernel_matrix_psd(n, d, seed, log_ell):
    """Property: Matérn-5/2 gram (with warping) is PSD for any inputs/params."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.random((n, d)))
    p = P.GPHyperParams(
        log_lengthscale=jnp.full((d,), log_ell),
        log_amplitude=jnp.asarray(0.2),
        log_noise=jnp.asarray(-2.0),
        log_warp_a=jnp.asarray(rng.normal(0, 0.4, d)),
        log_warp_b=jnp.asarray(rng.normal(0, 0.4, d)),
    )
    k = np.asarray(matern52_ard(x, x, p))
    evals = np.linalg.eigvalsh(k + 1e-9 * np.eye(n))
    assert evals.min() > -1e-7


def test_kernel_diag_equals_amplitude():
    x, _ = _data()
    p = P.default_params(3)
    k = matern52_ard(x, x, p)
    amp2 = float(jnp.exp(2 * p.log_amplitude))
    np.testing.assert_allclose(np.diag(np.asarray(k)), amp2, rtol=1e-9)


@settings(max_examples=40, deadline=None)
@given(st.floats(0.001, 0.999), st.floats(0.002, 0.998),
       st.floats(-1.2, 1.2), st.floats(-1.2, 1.2))
def test_warping_monotone(x1, x2, la, lb):
    """Property: the Kumaraswamy CDF warp is monotone increasing."""
    lo, hi = sorted([x1, x2])
    if hi - lo < 1e-6:
        return
    a, b = jnp.exp(la), jnp.exp(lb)
    w_lo = float(kumaraswamy_cdf(jnp.asarray(lo), a, b))
    w_hi = float(kumaraswamy_cdf(jnp.asarray(hi), a, b))
    assert w_hi >= w_lo - 1e-12


def test_warp_identity_at_zero_logs():
    x = jnp.asarray(np.random.default_rng(0).random((6, 4)))
    w = warp_inputs(x, jnp.zeros(4), jnp.zeros(4))
    np.testing.assert_allclose(np.asarray(w), np.asarray(x), atol=1e-12)


def test_posterior_interpolates_noiseless():
    x, y = _data()
    p = P.default_params(3)._replace(log_noise=jnp.asarray(np.log(1e-4)))
    post = G.fit_gp(x, y, p)
    mu, var = G.predict(post, x)
    np.testing.assert_allclose(np.asarray(mu), np.asarray(y), atol=1e-2)
    assert float(jnp.max(var)) < 1e-2


def test_posterior_variance_grows_away_from_data():
    x, y = _data()
    p = P.default_params(3)
    post = G.fit_gp(x, y, p)
    _, var_near = G.predict(post, x[:1])
    _, var_far = G.predict(post, jnp.asarray([[10.0, -10.0, 10.0]]))
    assert float(var_far[0]) > float(var_near[0])


def test_batched_posterior_matches_single():
    x, y = _data()
    p = P.default_params(3)
    batch = jax.tree.map(lambda a: jnp.stack([a, a]), p)
    post_b = G.fit_posterior_batch(x, y, batch)
    post_s = G.fit_gp(x, y, p)
    xs = x[:4]
    mu_b, var_b = G.predict(post_b, xs)
    mu_s, var_s = G.predict(post_s, xs)
    np.testing.assert_allclose(mu_b[0], mu_s, atol=1e-10)
    np.testing.assert_allclose(mu_b[1], mu_s, atol=1e-10)
    np.testing.assert_allclose(var_b[0], var_s, atol=1e-10)


@pytest.mark.parametrize("batched", [False, True], ids=["single", "S3"])
@pytest.mark.parametrize("pad", [0, 12], ids=["exact", "bucket-padded"])
def test_predict_cached_inverse_matches_solve(batched, pad):
    """``predict`` through the cached L⁻¹ (a matmul) agrees with the
    triangular solve of the factor in μ, σ² and ∂EI/∂x — the quantities the
    acquisition refinement reads at every Adam step."""
    from repro.core.acquisition import expected_improvement

    x, y = _data()
    n, d = x.shape
    mask = None
    if pad:
        # bucket padding: masked rows whose factor rows are identity
        x = jnp.concatenate([x, jnp.full((pad, d), 0.37)], axis=0)
        y = jnp.concatenate([y, jnp.full((pad,), 5.0)], axis=0)
        mask = jnp.arange(n + pad) < n
    p = P.default_params(d)
    if batched:
        shift = jnp.asarray([-0.3, 0.0, 0.4])
        p = jax.tree.map(lambda a: jnp.stack([a, a, a]), p)
        p = p._replace(
            log_lengthscale=p.log_lengthscale + shift[:, None],
            log_amplitude=p.log_amplitude - 0.5 * shift,
        )
        post_inv = G.fit_posterior_batch(x, y, p, mask, with_inverse=True)
    else:
        post_inv = G.fit_gp(x, y, p, mask, with_inverse=True)
    post_sol = post_inv._replace(chol_inv=None)
    xs = jnp.asarray(np.random.default_rng(3).random((7, d)))
    y_best = jnp.min(y[:n])

    def ei(post, xq):
        mu, var = G.predict(post, xq)
        return jnp.sum(expected_improvement(mu, var, y_best))

    mu_i, var_i = G.predict(post_inv, xs)
    mu_s, var_s = G.predict(post_sol, xs)
    assert mu_i.shape == mu_s.shape == ((3, 7) if batched else (7,))
    np.testing.assert_allclose(mu_i, mu_s, rtol=1e-10, atol=0)
    np.testing.assert_allclose(var_i, var_s, rtol=1e-10, atol=0)
    g_i = jax.grad(ei, argnums=1)(post_inv, xs)
    g_s = jax.grad(ei, argnums=1)(post_sol, xs)
    np.testing.assert_allclose(g_i, g_s, rtol=1e-10, atol=0)
