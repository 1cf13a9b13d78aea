"""Backend dispatcher for fused anchor scoring: ``acq_score``.

``backend="xla"`` is the production composition the engine always had
(``gp.predict`` + closed-form acquisition, three XLA ops).
``backend="pallas"`` pads/packs and invokes the fused kernel: one pass per
decision over the anchor grid, compiled on a TPU and interpreted on the CPU
(``repro.kernels.resolve_interpret``; other platforms are refused).

The kernel's solve is the matmul L⁻¹K*ᵀ. The inverted factor comes from the
posterior's ``chol_inv`` cache when the engine threaded it through
(``fit_posterior_batch(with_inverse=True)`` + O(n²) maintenance in the
rank-1 append — no per-decision inversion at all); otherwise it is computed
here, once per call — O(n³/3) per GPHP sample against the O(A·n²) anchor
sweep it feeds (the paper's grids use A ≥ n). Padded train rows extend the
factor with an identity block (as in ``gp.incremental.grow_posterior``),
whose inverse is again identity, keeping padded rows exactly inert.

Dtype policy: compiled on the TPU, inputs are cast to f32 (the chip's
dtype). Interpreted on the CPU the kernel runs in the anchors' own dtype, so
the x64 test session gets f64 parity against the XLA path and f32 inputs
rehearse the chip's arithmetic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import acquisition as A
from repro.core.gp.gp import GPPosterior, _triangular_inverse, predict
from repro.kernels import resolve_interpret
from repro.kernels.acq_score.kernel import acq_score_pallas, tiling
from repro.kernels.matern52.kernel import exp_accurate
from repro.kernels.matern52.ops import scaled_inputs

__all__ = ["acq_score", "acq_score_multi"]


def _pad_to(x: jax.Array, size: int, axis: int) -> jax.Array:
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pallas_scores(
    post: GPPosterior,
    alphas: jax.Array,  # (S, M, n) or (M, n) head alphas over post's factor
    x_star: jax.Array,  # (m, d)
    mode: str,
    num_con: int,
    small: tuple,  # (tcon, y_best, has_feasible, weights, y_best_w) in dt
    interpret: bool,
) -> jax.Array:
    """Pad, pack and run the fused kernel: (S, m) scores (S = 1 unbatched)."""
    batched = post.chol.ndim == 3
    chol = post.chol if batched else post.chol[None]
    dt = x_star.dtype if interpret else jnp.float32
    params = jax.tree.map(
        lambda p: (p if batched else p[None]).astype(dt), post.params
    )
    alphas = alphas if alphas.ndim == 3 else alphas[None]

    m, d = x_star.shape
    n = chol.shape[-1]
    mpad, tile_a, npad, tile_r = tiling(m, n)
    dpad = max(8, -(-d // 8) * 8)

    # warped, lengthscale-scaled coordinates per sample, by XLA in the
    # kernel's dtype; padded features and rows are zero
    def scaled(x, rows):
        s = scaled_inputs(x.astype(dt), params)
        return _pad_to(_pad_to(s, rows, 1), dpad, 2)

    anchors = scaled(x_star, mpad)
    train_t = jnp.swapaxes(scaled(post.x_train, npad), 1, 2)
    mask = _pad_to(post.mask.astype(dt)[None, :], npad, 1)

    # identity-extend the (inverted) factor over padded rows; block-diagonal
    # triangular matrices invert blockwise, so padding and inversion commute.
    def ident_pad(t):
        t = _pad_to(_pad_to(t.astype(dt), npad, 1), npad, 2)
        if npad > n:
            diag = jnp.arange(n, npad)
            t = t.at[:, diag, diag].set(1.0)
        return t

    if post.chol_inv is not None:
        linv = ident_pad(post.chol_inv if batched else post.chol_inv[None])
    else:
        linv = _triangular_inverse(ident_pad(chol))
    alphasp = _pad_to(alphas.astype(dt), npad, 2)
    amp2 = exp_accurate(2.0 * params.log_amplitude)[:, None, None]

    out = acq_score_pallas(
        anchors, train_t, linv, alphasp, mask, amp2,
        *(jnp.asarray(v, dt) for v in small),
        mode=mode, num_con=num_con, tile_a=tile_a, tile_r=tile_r,
        interpret=interpret,
    )  # (S, mpad)
    return out[:, :m].astype(x_star.dtype)


def acq_score(
    post: GPPosterior,
    x_star: jax.Array,  # (m, d) anchor locations in the unit cube
    y_best: jax.Array,  # scalar: best standardized observation
    *,
    acq: str = "ei",
    kappa: float = 2.0,
    backend: str = "xla",
    interpret: bool | None = None,
) -> jax.Array:
    """Acquisition values at ``x_star``: (S, m) if the posterior carries S
    GPHP samples, else (m,). Larger is better. ``acq``: "ei" | "lcb"."""
    if acq not in ("ei", "lcb"):
        raise ValueError(f"unsupported acquisition {acq!r}")
    if backend == "xla":
        mu, var = predict(post, x_star, backend="xla")
        if acq == "ei":
            return A.expected_improvement(mu, var, y_best)
        return A.lcb(mu, var, kappa)
    if backend != "pallas":
        raise ValueError(f"unknown acq_score backend {backend!r}")

    one = jnp.ones((1, 1))
    small = (
        jnp.zeros((1, 1)),  # tcon: no constraints
        jnp.reshape(y_best, (1, 1)),
        one,
        jnp.reshape(kappa, (1, 1)) if acq == "lcb" else one,
        jnp.zeros((1, 1)),
    )
    out = _pallas_scores(
        post, post.alpha[..., None, :], x_star, acq, 0, small,
        resolve_interpret(interpret),
    )
    return out if post.chol.ndim == 3 else out[0]


def acq_score_multi(
    post: GPPosterior,
    head,  # repro.core.optimize_acq.MultiMetricHead (duck-typed pytree)
    x_star: jax.Array,  # (m, d) anchor locations in the unit cube
    *,
    mode: str = "constrained",
    backend: str = "xla",
    interpret: bool | None = None,
) -> jax.Array:
    """Multi-head acquisition values at ``x_star``: (S, m), larger is
    better. ``mode``: "constrained" (EI₀ · Π Φ feasibility) | "pareto"
    (random-scalarization EI averaged over the head's weight draws) |
    "rungs" (resource-weighted per-head EI over the multi-fidelity rung
    heads — scores f(x, r) jointly across the rung grid) | "cost"
    (EI-per-unit-cost: EI on head 0 discounted by exp(−η · mean of the
    standardized log-cost head 1), η in ``weights[0, 0]``).

    ``backend="xla"`` is the production composition
    (``gp.multi.predict_heads`` + ``multimetric.acquisition`` /
    ``gp.per_resource``); ``backend="pallas"`` runs the fused kernel —
    warp + cross-gram + cached-factor solve once per (GPHP-sample ×
    anchor-tile), the extra heads amortized as matvecs against the shared
    gram."""
    if mode not in ("constrained", "pareto", "rungs", "cost"):
        raise ValueError(f"unsupported mode {mode!r}")
    if backend == "xla":
        from repro.core.gp.multi import MultiOutputPosterior, predict_heads
        from repro.core.gp.per_resource import rung_weighted_ei
        from repro.core.multimetric.acquisition import (
            constrained_ei,
            scalarized_ei,
        )

        mu, var = predict_heads(
            MultiOutputPosterior(post, head.alphas), x_star, backend="xla"
        )
        if mode == "constrained":
            return constrained_ei(
                mu, var, head.y_best, head.t_std, head.has_feasible
            )
        if mode == "rungs":
            return rung_weighted_ei(mu, var, head.y_best_w, head.weights[0])
        if mode == "cost":
            return A.expected_improvement(
                mu[:, 0, :], var, head.y_best
            ) * jnp.exp(-head.weights[0, 0] * mu[:, 1, :])
        return scalarized_ei(mu, var, head.weights, head.y_best_w, head.t_std)
    if backend != "pallas":
        raise ValueError(f"unknown acq_score backend {backend!r}")

    num_con = int(head.t_std.shape[0])
    tcon = head.t_std.reshape(-1, 1) if num_con else jnp.zeros((1, 1))
    if mode == "constrained":
        weights = ybw = jnp.zeros((1, 1))
    else:
        # pareto: weights (W, K) draws with ybw (W, 1) scalarized incumbents;
        # rungs: weights (1, M) rung-weight row with ybw (M, 1) per-head
        # incumbents; cost: weights (1, 1) eta with ybw a (1, 1) dummy —
        # the kernel takes each small operand whole.
        weights = head.weights
        ybw = head.y_best_w.reshape(-1, 1)
    small = (
        tcon,
        jnp.reshape(head.y_best, (1, 1)),
        jnp.reshape(head.has_feasible, (1, 1)),
        weights,
        ybw,
    )
    return _pallas_scores(
        post, head.alphas, x_star, mode, num_con, small,
        resolve_interpret(interpret),
    )
