"""GP regression core: posterior, marginal likelihood, prediction (paper §4.2).

Model:  f ~ GP(0, K_θ),   y | f(x) ~ N(f(x), σ₀²)

Observations are standardized (zero mean / unit std) by the caller, so the
zero-mean GP holds without loss of generality (paper §4.2).

Shape-bucketing: BO refits the GP after every new observation, which would
trigger an XLA recompile per dataset size. All functions therefore take a
boolean ``mask`` over rows of (X, y); callers pad to the next bucket size.
Masked rows are made *exactly* inert by pinning their kernel rows/cols to the
identity and their targets to zero:

    K̃ij = Kij·mi·mj + δij·(1 − mi·mj)   ⇒   log|K̃| and yᵀK̃⁻¹y are unaffected.

MCMC support: every function ``vmap``s cleanly over a leading sample axis on
``params`` — ``fit_posterior_batch`` does exactly that for the S slice-sampling
draws, and ``predict`` then returns per-sample means/variances.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.gp.kernels import gram
from repro.core.gp.params import GPHyperBounds, GPHyperParams

__all__ = [
    "GPPosterior",
    "log_marginal_likelihood",
    "log_posterior_density",
    "fit_gp",
    "fit_posterior_batch",
    "predict",
]

_JITTER = 1e-8
_LOG2PI = 1.8378770664093453


class GPPosterior(NamedTuple):
    """Cholesky-factorized GP posterior. Fields may carry a leading MCMC
    sample axis (S, ...) — produced by ``fit_posterior_batch``.

    Note: this is a pure pytree (jit/vmap-safe); the gram ``backend`` is
    passed separately as a static argument where needed.

    ``chol_inv`` (optional) caches L⁻¹. ``predict`` then computes L⁻¹k* as
    a matmul instead of a triangular solve (the acquisition refinement
    differentiates through it at every step), and the fused Pallas
    anchor-scoring kernel (``repro.kernels.acq_score``) reads it for its
    in-VMEM solve. It is maintained with the same cost profile as the
    factor: built once per refit (``with_inverse=True``), updated in O(n²)
    by the rank-1 border append, identity-padded on bucket growth."""

    x_train: jax.Array  # (n, d) encoded (unwarped) inputs
    mask: jax.Array  # (n,) bool — valid rows
    chol: jax.Array  # (..., n, n) lower Cholesky of K̃ + σ²I
    alpha: jax.Array  # (..., n)  K̃⁻¹ y
    params: GPHyperParams  # (...,) GPHPs
    chol_inv: Optional[jax.Array] = None  # (..., n, n) cached L⁻¹

    @property
    def num_samples(self) -> int:
        return self.chol.shape[0] if self.chol.ndim == 3 else 1


def _masked_kernel(
    x: jax.Array,
    params: GPHyperParams,
    mask: jax.Array,
    backend: str,
) -> jax.Array:
    n = x.shape[0]
    k = gram(x, x, params, backend=backend)
    mm = (mask[:, None] & mask[None, :]).astype(k.dtype)
    eye = jnp.eye(n, dtype=k.dtype)
    noise = jnp.exp(2.0 * params.log_noise) + _JITTER
    # masked rows/cols become identity; live diagonal gets the noise.
    return k * mm + eye * (1.0 - mm) + eye * mm * noise


def log_marginal_likelihood(
    x: jax.Array,
    y: jax.Array,
    params: GPHyperParams,
    mask: Optional[jax.Array] = None,
    *,
    backend: str = "xla",
) -> jax.Array:
    """log p(y | X, θ) for the live rows. Scalar."""
    n = x.shape[0]
    if mask is None:
        mask = jnp.ones(n, dtype=bool)
    y = jnp.where(mask, y, 0.0)
    kmat = _masked_kernel(x, params, mask, backend)
    chol = jnp.linalg.cholesky(kmat)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y)
    quad = jnp.dot(y, alpha)
    # masked rows contribute log(1)=0 to the logdet and 0 to the quad term.
    logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(chol)))
    n_live = jnp.sum(mask)
    return -0.5 * (quad + logdet + n_live * _LOG2PI)


def log_posterior_density(
    x: jax.Array,
    y: jax.Array,
    packed: jax.Array,
    bounds: GPHyperBounds,
    mask: Optional[jax.Array] = None,
    *,
    backend: str = "xla",
) -> jax.Array:
    """Unnormalized log posterior over the *packed* GPHP vector:
    MLL + weak Gaussian prior centered mid-box; −inf outside the box
    (the paper's hard stability bounds)."""
    d = x.shape[-1]
    inside = jnp.all((packed >= bounds.lower) & (packed <= bounds.upper))
    params = GPHyperParams.unpack(packed, d)
    mll = log_marginal_likelihood(x, y, params, mask, backend=backend)
    prior_std = jnp.maximum(bounds.width / 4.0, 1e-6)
    log_prior = -0.5 * jnp.sum(((packed - bounds.center) / prior_std) ** 2)
    return jnp.where(inside, mll + log_prior, -jnp.inf)


def _triangular_inverse(chol: jax.Array) -> jax.Array:
    """L⁻¹ for a (batch of) lower factor(s) — identity rows stay identity."""
    eye = jnp.broadcast_to(jnp.eye(chol.shape[-1], dtype=chol.dtype), chol.shape)
    return jax.lax.linalg.triangular_solve(chol, eye, left_side=True, lower=True)


def fit_gp(
    x: jax.Array,
    y: jax.Array,
    params: GPHyperParams,
    mask: Optional[jax.Array] = None,
    *,
    backend: str = "xla",
    with_inverse: bool = False,
) -> GPPosterior:
    """Factorize the posterior for a single GPHP setting."""
    n = x.shape[0]
    if mask is None:
        mask = jnp.ones(n, dtype=bool)
    y = jnp.where(mask, y, 0.0)
    kmat = _masked_kernel(x, params, mask, backend)
    chol = jnp.linalg.cholesky(kmat)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y)
    return GPPosterior(
        x_train=x,
        mask=mask,
        chol=chol,
        alpha=alpha,
        params=params,
        chol_inv=_triangular_inverse(chol) if with_inverse else None,
    )


def fit_posterior_batch(
    x: jax.Array,
    y: jax.Array,
    params_batch: GPHyperParams,
    mask: Optional[jax.Array] = None,
    *,
    backend: str = "xla",
    with_inverse: bool = False,
) -> GPPosterior:
    """Factorize once per MCMC sample (leading axis S on ``params_batch``)."""
    n = x.shape[0]
    if mask is None:
        mask = jnp.ones(n, dtype=bool)

    def one(p: GPHyperParams):
        post = fit_gp(x, y, p, mask, backend=backend)
        return post.chol, post.alpha

    chol, alpha = jax.vmap(one)(params_batch)
    return GPPosterior(
        x_train=x,
        mask=mask,
        chol=chol,
        alpha=alpha,
        params=params_batch,
        chol_inv=_triangular_inverse(chol) if with_inverse else None,
    )


def predict(
    post: GPPosterior, x_star: jax.Array, *, backend: str = "xla"
) -> tuple[jax.Array, jax.Array]:
    """Posterior marginals at x_star: (mu, var), each (S, m) if the posterior
    holds S MCMC samples, else (m,). Variance includes the latent-f variance
    only (not observation noise), matching EI-on-f semantics.

    With a cached ``chol_inv`` the solve L⁻¹k* is a full-precision matmul
    against it; without one it is a triangular solve against ``chol``. The
    choice rests on the posterior's pytree structure, so it is fixed at
    trace time."""
    batched = post.chol.ndim == 3

    def one(chol, chol_inv, alpha, params):
        k_star = gram(post.x_train, x_star, params, backend=backend)  # (n, m)
        k_star = k_star * post.mask[:, None].astype(k_star.dtype)
        mu = k_star.T @ alpha  # (m,)
        if chol_inv is None:
            v = jax.scipy.linalg.solve_triangular(chol, k_star, lower=True)
        else:
            v = jnp.matmul(
                chol_inv, k_star, precision=jax.lax.Precision.HIGHEST
            )  # (n, m)
        amp2 = jnp.exp(2.0 * params.log_amplitude)
        var = jnp.maximum(amp2 - jnp.sum(v * v, axis=0), 1e-12)
        return mu, var

    args = (post.chol, post.chol_inv, post.alpha, post.params)
    return jax.vmap(one)(*args) if batched else one(*args)
