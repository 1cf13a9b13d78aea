"""Acquisition optimization (paper §4.3).

"the resulting pseudo-random grid [a Sobol sequence populating the search
space as densely as possible] is used as a set of anchor points to initialize
the local optimization of the EI. This scales linearly in the number of
locations and works well in practice."

Pipeline (all jitted, shapes static per (n_bucket, d, S)):
  1. evaluate the integrated acquisition at ``num_anchors`` Sobol points;
  2. mask anchors within ``exclusion_radius`` of pending candidates (the
     paper's "making sure not to select one of the L−1 pending candidates");
  3. take the ``num_refine`` best anchors and run projected-Adam ascent on the
     acquisition (jax.grad flows through the GP posterior), clipping to the
     unit cube;
  4. return refined candidates ranked by acquisition value.

Backends: ``AcqOptConfig.backend`` selects how stage 1 (and the final
re-ranking) scores anchors. ``"pallas"`` dispatches EI/LCB to the fused
predict+acquisition kernel (``repro.kernels.acq_score``): cross-gram,
cached-Cholesky solve and the closed form run in one VMEM pass per
(GPHP-sample × anchor-tile), instead of three XLA ops with HBM round-trips.
Stage 3 (gradient refinement) evaluates through the XLA composition —
``jax.grad`` must flow through the posterior, which ``pallas_call`` does not
provide — so the hot dense-grid sweep is fused while the 8-point ascent keeps
exact f64 gradients. The refinement reads the posterior's cached L⁻¹: each
step's L⁻¹k* (forward and VJP) is a matmul against it, not a triangular
solve of the factor (``gp.predict``); a posterior without the cache, such as
a per-head factor, falls back to the solve.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import acquisition as A
from repro.core.gp.gp import GPPosterior, predict

__all__ = [
    "AcqOptConfig",
    "MultiAcqSpec",
    "MultiMetricHead",
    "optimize_acquisition",
    "optimize_acquisition_multi",
]


class AcqOptConfig(NamedTuple):
    acq: str = "ei"  # "ei" | "lcb" | "ts"
    num_anchors: int = 1024
    num_refine: int = 8  # anchors promoted to gradient refinement
    refine_steps: int = 25
    refine_lr: float = 0.05
    lcb_kappa: float = 2.0
    exclusion_radius: float = 0.02  # L∞ radius (unit cube) around pending pts
    backend: str = "xla"  # anchor-scoring backend ("xla" | "pallas" fused kernel)


def _acq_values(
    post: GPPosterior,
    x: jax.Array,
    y_best: jax.Array,
    cfg: AcqOptConfig,
    key: jax.Array,
    *,
    differentiable: bool = False,
) -> jax.Array:
    """Integrated acquisition at x: (m, d) -> (m,). Larger is better.

    ``differentiable=True`` forces the XLA predict+closed-form composition
    (the gradient-refinement stage needs jax.grad); otherwise EI/LCB on the
    pallas backend go through the fused anchor-scoring kernel."""
    if cfg.acq in ("ei", "lcb") and cfg.backend == "pallas" and not differentiable:
        from repro.kernels.acq_score.ops import acq_score

        vals = acq_score(
            post, x, y_best, acq=cfg.acq, kappa=cfg.lcb_kappa, backend="pallas"
        )
        return A.integrate_over_samples(vals)
    mu, var = predict(post, x, backend="xla" if differentiable else cfg.backend)
    if cfg.acq == "ei":
        vals = A.expected_improvement(mu, var, y_best)
    elif cfg.acq == "lcb":
        vals = A.lcb(mu, var, cfg.lcb_kappa)
    elif cfg.acq == "ts":
        # Thompson: negative draws so larger is better; the argmax anchor is
        # the Thompson-sample minimizer.
        vals = -A.thompson_draws(mu, var, key)
    else:
        raise ValueError(f"unknown acquisition {cfg.acq!r}")
    return A.integrate_over_samples(vals)


def _stage(name: str, fn, *args):
    """``fn(*args)`` as a nested jitted call under the named scope ``name``.

    XLA inlines the call, so the compiled program is the same. The call is
    what carries the scope into every instruction's ``op_name`` when JAX
    writes locations without full tracebacks, as the entry points that use
    the persistent cache do (``repro.compile_cache``): a scope alone then
    survives only on instructions that are themselves calls."""
    with jax.named_scope(name):
        return jax.jit(fn)(*args)


def _refine_and_rank(
    masked_acq,
    anchors: jax.Array,
    cfg: AcqOptConfig,
) -> tuple[jax.Array, jax.Array]:
    """Shared stage 2–4 of the pipeline: top-k anchors → projected-Adam
    ascent on the (masked) acquisition → re-rank. ``masked_acq(x,
    differentiable)`` scores (m, d) → (m,), larger is better.

    The stages run as ``acq.anchors``, ``acq.refine`` and ``acq.rerank``
    (``_stage``), so a device trace can be split by stage."""

    def top_anchors(anchors):
        anchor_vals = masked_acq(anchors)  # (num_anchors,)
        top_idx = jax.lax.top_k(anchor_vals, cfg.num_refine)[1]
        return anchors[top_idx], anchor_vals[top_idx]  # (num_refine, ·)

    x0, x0_vals = _stage("acq.anchors", top_anchors, anchors)

    # --- projected Adam ascent on the acquisition -------------------------
    # (differentiable=True: refinement keeps the XLA path for jax.grad)
    def acq_scalar(x_single: jax.Array) -> jax.Array:
        return masked_acq(x_single[None, :], differentiable=True)[0]

    grad_fn = jax.vmap(jax.grad(acq_scalar))

    def step(carry, _):
        x, m, v, t = carry
        g = grad_fn(x)
        g = jnp.where(jnp.isfinite(g), g, 0.0)
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        mhat = m / (1.0 - 0.9 ** (t + 1.0))
        vhat = v / (1.0 - 0.999 ** (t + 1.0))
        x = jnp.clip(x + cfg.refine_lr * mhat / (jnp.sqrt(vhat) + 1e-8), 0.0, 1.0)
        return (x, m, v, t + 1.0), None

    def refine(x0):
        (x_ref, _, _, _), _ = jax.lax.scan(
            step,
            (x0, jnp.zeros_like(x0), jnp.zeros_like(x0), jnp.asarray(0.0)),
            None,
            length=cfg.refine_steps,
        )
        return x_ref

    x_ref = _stage("acq.refine", refine, x0)

    def rerank(x_ref, x0, x0_vals):
        ref_vals = masked_acq(x_ref)
        # A refined point may have walked into the exclusion zone; keep the
        # anchor value as fallback so ranking never returns −inf when anchors
        # were valid.
        use_ref = ref_vals >= x0_vals
        final_x = jnp.where(use_ref[:, None], x_ref, x0)
        final_v = jnp.where(use_ref, ref_vals, x0_vals)
        order = jnp.argsort(-final_v)
        return final_x[order], final_v[order]

    return _stage("acq.rerank", rerank, x_ref, x0, x0_vals)


def _pending_masked(score, pending: jax.Array, pending_mask: jax.Array,
                    cfg: AcqOptConfig):
    """Wrap a scorer with the §4.4 pending-exclusion mask (L∞ radius)."""

    def masked_acq(x: jax.Array, differentiable: bool = False) -> jax.Array:
        vals = score(x, differentiable)
        if pending.shape[0] > 0:
            # L∞ distance to every pending point
            dists = jnp.max(
                jnp.abs(x[:, None, :] - pending[None, :, :]), axis=-1
            )  # (m, p)
            near = jnp.any(
                (dists < cfg.exclusion_radius) & pending_mask[None, :], axis=-1
            )
            vals = jnp.where(near, -jnp.inf, vals)
        return vals

    return masked_acq


@functools.partial(jax.jit, static_argnames=("cfg",))
def optimize_acquisition(
    post: GPPosterior,
    anchors: jax.Array,  # (num_anchors, d) Sobol points in the unit cube
    y_best: jax.Array,  # scalar: best standardized observation
    pending: jax.Array,  # (p, d) encoded pending candidates (may be padding)
    pending_mask: jax.Array,  # (p,) bool
    key: jax.Array,
    cfg: AcqOptConfig = AcqOptConfig(),
) -> tuple[jax.Array, jax.Array]:
    """Return (candidates, acq_values): (num_refine, d) refined points sorted
    best-first, with pending-exclusion applied."""
    k_ts, _ = jax.random.split(key)

    def score(x: jax.Array, differentiable: bool) -> jax.Array:
        return _acq_values(post, x, y_best, cfg, k_ts,
                           differentiable=differentiable)

    masked_acq = _pending_masked(score, pending, pending_mask, cfg)
    return _refine_and_rank(masked_acq, anchors, cfg)


class MultiAcqSpec(NamedTuple):
    """Static (hashable) shape of a multi-metric acquisition problem —
    jointly with ``AcqOptConfig`` this keys the jit cache.

    ``mode="rungs"`` is the multi-fidelity f(x, r) acquisition: heads are
    [objective, rung 0, …, rung R−1] over the shared factor, scored as a
    weighted per-head EI (``repro.core.gp.per_resource.rung_weighted_ei``);
    ``num_objectives`` is then the head count 1+R and there are no
    constraints.

    ``mode="cost"`` is EI-per-unit-cost (``BOConfig.cost_aware``): heads are
    [objective, standardized log-cost] over the shared factor, scored as
    EI(head 0) · exp(−η · mean(head 1)) with η in ``weights[0, 0]``;
    ``num_objectives`` is 2 and there are no constraints."""

    mode: str  # "constrained" | "pareto" | "rungs" | "cost"
    num_objectives: int
    num_constraints: int


class MultiMetricHead(NamedTuple):
    """Per-decision array state of the multi-metric acquisition (a pytree,
    traced): everything beyond the shared-factor posterior that the scorer
    needs. Objectives lead, constraints trail (the ``MetricSet`` order).

    ``weights``/``y_best_w`` are the random-scalarization draws of Pareto
    mode and are empty (W=0) in constrained mode; ``y_best``/``has_feasible``
    drive constrained EI and are ignored in Pareto mode.

    ``head_posts`` is empty in the default shared-factor layout. With
    ``BOConfig.per_head_gphp`` it carries one ``GPPosterior`` per extra head
    (head 1 first), each fitted under its own GPHP chain; the scorer then
    predicts every head through its own factor (per-head variances) instead
    of the shared-factor alpha block, and ``alphas`` degenerates to the
    objective column. The tuple length is part of the pytree structure, so
    the two layouts jit-compile separately and the default path is untouched."""

    alphas: jax.Array  # (S, M, n) all-head K̃⁻¹y (head 0 = objective)
    t_std: jax.Array  # (C,) standardized signed constraint thresholds
    y_best: jax.Array  # () best *feasible* standardized objective
    has_feasible: jax.Array  # () bool: feasible incumbent exists
    weights: jax.Array  # (W, K) simplex scalarization draws
    y_best_w: jax.Array  # (W,) best observed scalarized value per draw
    head_posts: tuple = ()  # per-head GPPosteriors (per_head_gphp only)


def _acq_values_multi(
    post: GPPosterior,
    head: MultiMetricHead,
    x: jax.Array,
    cfg: AcqOptConfig,
    spec: MultiAcqSpec,
    *,
    differentiable: bool = False,
) -> jax.Array:
    """Integrated multi-metric acquisition at x: (m, d) → (m,). The fused
    Pallas multi-head scorer serves the dense anchor sweep; gradient
    refinement always goes through the jnp composition (jax.grad)."""
    from repro.core.gp.multi import MultiOutputPosterior, predict_heads
    from repro.core.gp.per_resource import rung_weighted_ei
    from repro.core.multimetric.acquisition import constrained_ei, scalarized_ei

    def closed_form(mu, var):
        if spec.mode == "constrained":
            return constrained_ei(
                mu, var, head.y_best, head.t_std, head.has_feasible
            )
        if spec.mode == "rungs":
            # weights is the (1, R+1) acquisition row; y_best_w the (R+1,)
            # per-head incumbents (shared variance: var is (S, m)).
            return rung_weighted_ei(mu, var, head.y_best_w, head.weights[0])
        if spec.mode == "cost":
            # EI on the objective head discounted by the predicted
            # standardized log-cost (head 1 mean); eta rides weights[0, 0].
            return A.expected_improvement(
                mu[:, 0, :], var, head.y_best
            ) * jnp.exp(-head.weights[0, 0] * mu[:, 1, :])
        return scalarized_ei(mu, var, head.weights, head.y_best_w, head.t_std)

    if head.head_posts:
        # per-head layout (BOConfig.per_head_gphp): every head predicts
        # through its own factor — variances are per-head, so the fused
        # shared-variance Pallas kernel does not apply and scoring stays on
        # the jnp composition for both the anchor sweep and refinement.
        backend = "xla" if differentiable else (
            "xla" if cfg.backend == "pallas" else cfg.backend
        )
        mu0, var0 = predict(post, x, backend=backend)
        mus, vrs = [mu0], [var0]
        for hp in head.head_posts:
            muh, varh = predict(hp, x, backend=backend)
            mus.append(muh)
            vrs.append(varh)
        mu = jnp.stack(mus, axis=1)  # (S, M, m)
        var = jnp.stack(vrs, axis=1)  # (S, M, m) per-head variances
        if spec.mode == "constrained":
            vals = constrained_ei(
                mu, var, head.y_best, head.t_std, head.has_feasible
            )
        else:
            vals = scalarized_ei(
                mu, var, head.weights, head.y_best_w, head.t_std
            )
        return A.integrate_over_samples(vals)
    if cfg.backend == "pallas" and not differentiable:
        from repro.kernels.acq_score.ops import acq_score_multi

        vals = acq_score_multi(post, head, x, mode=spec.mode, backend="pallas")
        return A.integrate_over_samples(vals)
    mp = MultiOutputPosterior(post, head.alphas)
    mu, var = predict_heads(
        mp, x, backend="xla" if differentiable else cfg.backend
    )
    return A.integrate_over_samples(closed_form(mu, var))


@functools.partial(jax.jit, static_argnames=("cfg", "spec"))
def optimize_acquisition_multi(
    post: GPPosterior,  # shared-factor posterior (objective head resident)
    head: MultiMetricHead,
    anchors: jax.Array,  # (num_anchors, d) Sobol points in the unit cube
    pending: jax.Array,  # (p, d) encoded pending candidates (may be padding)
    pending_mask: jax.Array,  # (p,) bool
    key: jax.Array,
    cfg: AcqOptConfig,
    spec: MultiAcqSpec,
) -> tuple[jax.Array, jax.Array]:
    """Multi-metric analogue of ``optimize_acquisition``: same Sobol-anchor
    → top-k → projected-Adam pipeline, scored by constrained EI or
    random-scalarization EI over the shared-factor multi-output posterior."""
    del key  # multi-metric modes are EI-based; no Thompson draws

    def score(x: jax.Array, differentiable: bool) -> jax.Array:
        return _acq_values_multi(
            post, head, x, cfg, spec, differentiable=differentiable
        )

    masked_acq = _pending_masked(score, pending, pending_mask, cfg)
    return _refine_and_rank(masked_acq, anchors, cfg)
