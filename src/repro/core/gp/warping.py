"""Kumaraswamy-CDF input warping (paper §4.2, following Snoek et al. 2014).

The paper: "An alternative, which is the default choice in AMT, is to consider
the CDF of the Kumaraswamy's distribution, which is more tractable than the CDF
of the Beta distribution."

    ω(x_j) = 1 - (1 - x_j^{a_j})^{b_j},   x_j ∈ [0, 1]

with (a_j, b_j) treated as extra GPHPs (merged into θ; see ``params.py``).
The warp is applied entry-wise to the encoded inputs before the kernel, i.e.
K(x, x') := K(ω(x), ω(x')) — the "overloaded covariance" of the paper.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["kumaraswamy_cdf", "warp_inputs"]

_EPS = 1e-6


def _exp_log(dtype):
    """(exp, log, log1p) for the warp in ``dtype``: XLA's own, except in
    float32, where ``exp_accurate``/``log_accurate`` (mul/add forms from the
    Matérn kernel package) stand in. A TPU v5e's float32 log misses by ~3.5e-4
    relative and its exp by ~5e-6, which moved the Pallas kernels' scores
    by ~4e-3; float64 keeps XLA's ops and its bits."""
    if dtype != jnp.float32:
        return jnp.exp, jnp.log, jnp.log1p
    from repro.kernels.matern52.kernel import exp_accurate, log_accurate

    return exp_accurate, log_accurate, lambda v: log_accurate(1.0 + v)


def kumaraswamy_cdf(x: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """Elementwise Kumaraswamy CDF, numerically safe at the cube boundary.

    x: (..., d) in [0,1];  a, b: broadcastable positive shapes.
    """
    exp, log, log1p = _exp_log(x.dtype)
    x = jnp.clip(x, _EPS, 1.0 - _EPS)
    # x^a = exp(a log x): stable since x is clipped away from 0.
    xa = exp(a * log(x))
    xa = jnp.clip(xa, _EPS, 1.0 - _EPS)
    return 1.0 - exp(b * log1p(-xa))


def warp_inputs(
    x: jax.Array,
    log_a: jax.Array,
    log_b: jax.Array,
) -> jax.Array:
    """Apply the entry-wise warp ω to encoded inputs.

    x: (..., d) in the unit cube. log_a/log_b: (d,) log-shapes (or with
    leading axes that broadcast against x); dims pinned to 0 (a=b=1) reduce
    *exactly* to identity up to boundary clipping — we make them literally
    identity so one-hot dims are untouched.
    """
    exp = _exp_log(x.dtype)[0]
    a = exp(log_a)
    b = exp(log_b)
    warped = kumaraswamy_cdf(x, a, b)
    identity = (jnp.abs(log_a) < 1e-7) & (jnp.abs(log_b) < 1e-7)
    return jnp.where(identity, x, warped)
