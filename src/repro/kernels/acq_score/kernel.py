"""Pallas TPU kernel: fused predict + acquisition over the Sobol anchor grid.

Anchor scoring is the per-decision hot path of the BO engine (paper §4.3):
every suggestion evaluates the integrated acquisition at ``num_anchors``
Sobol points, per GPHP MCMC sample. The XLA composition runs three separate
ops with an HBM round-trip between each:

    cross-gram (S·A·n)  →  triangular solve (S·A·n²)  →  acquisition (S·A)

This kernel fuses the whole chain per (GPHP-sample × anchor-tile) grid cell:
the Matérn-5/2 cross-gram row block against the cached train set is
computed in VMEM, the cached-factor solve for σ² streams the inverse factor
through VMEM, and the acquisition value is the only thing written back —
K* is never materialized off-chip. The Kumaraswamy warp and lengthscale
scaling run before the kernel, in XLA (ops.py): per sample they touch only
(m + n)·d coordinates (see ``repro.kernels.matern52.ops.scaled_inputs``).

Solve strategy: the dispatcher (ops.py) supplies the inverted lower factor
L⁻¹, so the in-kernel "triangular solve" V = L⁻¹K*ᵀ is an MXU matmul instead
of an n-step substitution recurrence. μ_j = K*·α_j reuses the cached alphas.

Grid: (S, anchor tiles, factor row tiles). The third axis streams L⁻¹ in
(tile_r, npad) row blocks and accumulates the column sums of squares of V,
so VMEM holds one row block at a time and every bucket size fits. K* and
the per-head means are computed once per anchor tile, at the first row step,
into VMEM scratch; the acquisition is evaluated at the last row step.

Heads and modes: ``alphas`` carries M heads over one shared factor (so σ is
common). ``mode`` picks the closed form applied in VMEM:

  * ``"ei"`` / ``"lcb"`` — single-head EI, or negated LCB (κ in ``weights``);
  * ``"constrained"`` — EI₀ · Π Φ over the trailing ``num_con`` heads;
  * ``"pareto"`` — random-scalarization EI averaged over the W weight rows;
  * ``"rungs"`` — resource-weighted per-head EI (multi-fidelity);
  * ``"cost"`` — EI₀ · exp(−η · μ₁), η in ``weights``.

Dtypes: the body computes in the dtype of its inputs (f32 on the chip; the
CPU interpreter may run f64). Every constant is a weak Python float or an
explicitly typed f32, so nothing promotes to f64 inside the body when the
process has ``jax_enable_x64`` on. Exponentials use ``exp_accurate`` and
the dots run at ``Precision.HIGHEST`` (Mosaic's f32 contraction), since the
chip's own ``exp`` and default-precision dot are not f32-accurate.

Masked-row contract (matches ``repro.core.gp.gp``): padded/masked train rows
have mask = 0, α = 0 and an identity row/col in L (hence in L⁻¹), so they
contribute exactly nothing to μ or σ².

Padding contract (enforced by ops.py, see ``tiling``): anchors padded to a
multiple of ``tile_a``, features to a multiple of 8 with zero coordinates,
train rows to a multiple of ``tile_r`` with mask = 0; padded anchor scores
are trimmed by the wrapper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.matern52.kernel import exp_accurate, matern52, sqdist

__all__ = ["acq_score_pallas", "ndtr", "tiling", "MODES", "TILE_A"]

MODES = ("ei", "lcb", "constrained", "pareto", "rungs", "cost")
TILE_A = 128  # minimum anchors per grid cell (lane-aligned)
_VMEM_TILE_ELEMS = 1 << 20  # cap on tile_a·npad (K*) and tile_r·npad (L⁻¹ rows)
_VMEM_LIMIT_BYTES = 96 * 1024 * 1024  # scoped VMEM for one cell (v5e: 128 MiB)

_INV_SQRT2 = 0.7071067811865476
_INV_SQRT2PI = 0.3989422804014327
_I0 = np.int32(0)  # block index: int32 even when x64 is on

# Normal CDF from mul/add/div/exp only: Mosaic has no lowering for lax.erf,
# so the kernel body evaluates the rational approximations XLA itself uses
# for f32 (Cephes erff/erfcf). The centre uses erf(x) = x·P(x²)/Q(x²)
# (absolute error below f32 rounding); the tails use erfc(x) =
# exp(−x²)/x · R(1/x²), relative error below f32 rounding, so EI keeps its
# relative accuracy far below the incumbent. The same polynomials run in the
# interpreter and on the chip.
_ERF_P = tuple(np.float32(c) for c in (
    -2.72614225801306e-10, 2.77068142495902e-08, -2.10102402082508e-06,
    -5.69250639462346e-05, -7.34990630326855e-04, -2.95459980854025e-03,
    -1.60960333262415e-02,
))
_ERF_Q = tuple(np.float32(c) for c in (
    -1.45660718464996e-05, -2.13374055278905e-04, -1.68282697438203e-03,
    -7.37332916720468e-03, -1.42647390514189e-02,
))
_ERFC_NEAR = tuple(np.float32(c) for c in (  # 1 <= x < 2
    2.326819970068386e-02, -1.387039388740657e-01, 3.687424674597105e-01,
    -5.824733027278666e-01, 6.210004621745983e-01, -4.944515323274145e-01,
    3.404879937665872e-01, -2.741127028184656e-01, 5.638259427386472e-01,
))
_ERFC_FAR = tuple(np.float32(c) for c in (  # x >= 2
    -1.047766399936249e01, 1.297719955372516e01, -7.495518717768503e00,
    2.921019019210786e00, -1.015265279202700e00, 4.218463358204948e-01,
    -2.820767439740514e-01, 5.641895067754075e-01,
))


def _split_even(total: int, cap: int, align: int) -> tuple:
    """(tile, padded total): the fewest ``align``-multiple tiles of at most
    about ``cap`` elements each that cover ``total``."""
    steps = -(-total // max(cap, align))
    per_step = -(-total // steps)
    tile = -(-per_step // align) * align
    return tile, tile * steps


def tiling(m: int, n: int) -> tuple:
    """Grid tiling for m anchors against n train rows:
    ``(mpad, tile_a, npad, tile_r)``.

    Row tiles of L⁻¹ are sized so a (tile_r, npad) block stays within the
    VMEM tile budget; anchor tiles so the (tile_a, npad) K* scratch does.
    Large anchor tiles amortize the per-cell streaming of L⁻¹ — with the
    paper's 1024-anchor grid and n ≤ 512 buckets the whole anchor sweep for a
    GPHP sample is one cell."""
    npad = max(8, -(-n // 8) * 8)
    tile_r, npad = _split_even(npad, _VMEM_TILE_ELEMS // npad, 8)
    mpad = -(-m // TILE_A) * TILE_A
    tile_a, mpad = _split_even(mpad, _VMEM_TILE_ELEMS // npad, TILE_A)
    return mpad, tile_a, npad, tile_r


# Shared in-kernel math (plain traced jnp, inlined into the kernel body).


def _polyval(coeffs, x):
    acc = coeffs[0]
    for c in coeffs[1:]:
        acc = acc * x + c
    return acc


def _erf(x):
    x = jnp.minimum(jnp.maximum(x, -4.0), 4.0)
    x2 = x * x
    return x * _polyval(_ERF_P, x2) / _polyval(_ERF_Q, x2)


def ndtr(z):
    """Standard normal CDF Φ(z) = erfc(−z/√2)/2, accurate to f32 rounding
    (relative in the lower tail), built from ops that lower in Mosaic."""
    t = -z * _INV_SQRT2
    a = jnp.abs(t)
    q = 1.0 / jnp.maximum(a, 1.0)
    y = q * q
    tail = exp_accurate(-a * a) * q * jnp.where(
        a < 2.0, _polyval(_ERFC_NEAR, y), _polyval(_ERFC_FAR, y)
    )
    tail = jnp.where(t < 0.0, 2.0 - tail, tail)
    return 0.5 * jnp.where(a < 1.0, 1.0 - _erf(t), tail)


def _ei_closed_form(mu, sigma, incumbent):
    """EI = σ·(γΦ(γ) + φ(γ)), clamped at 0 (rounds slightly negative for γ ≪ 0)."""
    gamma = (incumbent - mu) / sigma
    pdf = _INV_SQRT2PI * exp_accurate(-0.5 * gamma * gamma)
    return jnp.maximum(sigma * (gamma * ndtr(gamma) + pdf), 0.0)


def _dot(a, b, contract):
    return jax.lax.dot_general(
        a, b,
        dimension_numbers=(contract, ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=a.dtype,
    )


def _score(mode, num_con, mu, sigma, tcon, y_best, has_feas, weights, ybw):
    """Acquisition (1, tile_a) from head means mu (M, tile_a) and σ (1, tile_a)."""
    if mode == "ei":
        return _ei_closed_form(mu, sigma, y_best)
    if mode == "lcb":  # negated lower confidence bound; κ rides ``weights``
        return weights * sigma - mu
    feas = 1.0
    if num_con:
        first = mu.shape[0] - num_con
        phi = ndtr((tcon[:num_con] - mu[first:]) / sigma)  # (C, tile_a)
        feas = phi[0:1]
        for c in range(1, num_con):
            feas = feas * phi[c : c + 1]
    if mode == "constrained":
        e0 = _ei_closed_form(mu[0:1], sigma, y_best)
        return jnp.where(has_feas > 0.5, e0 * feas, feas)
    if mode == "rungs":
        # per-head EI against each head's own incumbent (σ shared), then one
        # weights-row contraction: f(x, r) over all rungs at once.
        return _dot(weights, _ei_closed_form(mu, sigma, ybw), ((1,), (0,)))
    if mode == "cost":
        # EI per unit cost: objective EI discounted by the predicted
        # standardized log-cost (head 1 mean); η rides ``weights``.
        e0 = _ei_closed_form(mu[0:1], sigma, y_best)
        return e0 * exp_accurate(-weights * mu[1:2])
    # "pareto" — random-scalarization EI averaged over the W draws
    num_obj = weights.shape[1]
    mu_s = _dot(weights, mu[:num_obj], ((1,), (0,)))  # (W, tile_a)
    sigma_s = sigma * jnp.sqrt(jnp.sum(weights * weights, axis=1, keepdims=True))
    ei_w = _ei_closed_form(mu_s, sigma_s, ybw)  # (W, tile_a)
    return jnp.sum(ei_w, axis=0, keepdims=True) * (1.0 / weights.shape[0]) * feas


def _acq_kernel(
    anchors_ref,  # (1, tile_a, dpad) scaled anchor tile, sample s
    train_ref,  # (1, dpad, npad) scaled train set, transposed, sample s
    linv_ref,  # (1, tile_r, npad) row block of L⁻¹, sample s
    alphas_ref,  # (1, M, npad) cached K̃⁻¹y_j for every head, sample s
    mask_ref,  # (1, npad) 1.0 on live train rows
    amp2_ref,  # (1, 1, 1) signal variance, sample s
    tcon_ref,  # (max(C,1), 1) standardized constraint thresholds
    ybest_ref,  # (1, 1) incumbent (standardized)
    feas_ref,  # (1, 1) 1.0 iff a feasible incumbent exists (constrained)
    weights_ref,  # (W, K) draws | (1, M) rung weights | (1, 1) κ or η
    ybw_ref,  # (W, 1) scalarized incumbents | (M, 1) per-head incumbents
    out_ref,  # (1, 1, tile_a) acquisition values
    kstar_ref,  # scratch (tile_a, npad): masked cross-gram K*
    mu_ref,  # scratch (M, tile_a): per-head means
    acc_ref,  # scratch (1, tile_a): Σ_rows (L⁻¹K*ᵀ)²
    *,
    mode: str,
    num_con: int,
):
    r = pl.program_id(2)
    amp2 = amp2_ref[0]  # (1, 1)

    @pl.when(r == 0)
    def _():
        k = matern52(sqdist(anchors_ref[0], train_ref[0]), amp2)
        k = k * mask_ref[...]  # (tile_a, npad); masked train rows inert
        kstar_ref[...] = k
        mu_ref[...] = _dot(alphas_ref[0], k, ((1,), (1,)))  # (M, tile_a)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # σ² = amp² − ‖L⁻¹K*ᵀ‖²_col, one row block of L⁻¹ per grid step.
    v = _dot(linv_ref[0], kstar_ref[...], ((1,), (1,)))  # (tile_r, tile_a)
    acc_ref[...] += jnp.sum(v * v, axis=0, keepdims=True)

    @pl.when(r == pl.num_programs(2) - 1)
    def _():
        sigma = jnp.sqrt(jnp.maximum(amp2 - acc_ref[...], 1e-12))  # (1, tile_a)
        out_ref[0] = _score(
            mode, num_con, mu_ref[...], sigma, tcon_ref[...], ybest_ref[...],
            feas_ref[...], weights_ref[...], ybw_ref[...],
        )


@functools.partial(
    jax.jit,
    static_argnames=("mode", "num_con", "tile_a", "tile_r", "interpret"),
)
def acq_score_pallas(
    anchors: jax.Array,  # (S, mpad, dpad) scaled, mpad % tile_a == 0
    train_t: jax.Array,  # (S, dpad, npad) scaled, npad % tile_r == 0
    linv: jax.Array,  # (S, npad, npad)
    alphas: jax.Array,  # (S, M, npad)
    mask: jax.Array,  # (1, npad)
    amp2: jax.Array,  # (S, 1, 1)
    tcon: jax.Array,  # (max(C,1), 1)
    y_best: jax.Array,  # (1, 1)
    has_feasible: jax.Array,  # (1, 1)
    weights: jax.Array,  # (W, K)
    y_best_w: jax.Array,  # (W', 1)
    *,
    mode: str = "ei",
    num_con: int = 0,
    tile_a: int = TILE_A,
    tile_r: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Per-sample acquisition at every anchor: (S, mpad)."""
    if mode not in MODES:
        raise ValueError(f"unsupported mode {mode!r}")
    s, m, d = anchors.shape
    npad = linv.shape[1]
    num_heads = alphas.shape[1]
    dt = anchors.dtype
    small = (tcon, y_best, has_feasible, weights, y_best_w)

    def whole(x):
        return pl.BlockSpec(x.shape, lambda i, j, r: (_I0,) * x.ndim)

    out = pl.pallas_call(
        functools.partial(_acq_kernel, mode=mode, num_con=num_con),
        grid=(s, m // tile_a, npad // tile_r),
        in_specs=[
            pl.BlockSpec((1, tile_a, d), lambda i, j, r: (i, j, _I0)),
            pl.BlockSpec((1, d, npad), lambda i, j, r: (i, _I0, _I0)),
            pl.BlockSpec((1, tile_r, npad), lambda i, j, r: (i, r, _I0)),
            pl.BlockSpec((1, num_heads, npad), lambda i, j, r: (i, _I0, _I0)),
            pl.BlockSpec((1, npad), lambda i, j, r: (_I0, _I0)),
            pl.BlockSpec((1, 1, 1), lambda i, j, r: (i, _I0, _I0)),
            *[whole(x) for x in small],
        ],
        out_specs=pl.BlockSpec((1, 1, tile_a), lambda i, j, r: (i, _I0, j)),
        out_shape=jax.ShapeDtypeStruct((s, 1, m), dt),
        scratch_shapes=[
            pltpu.VMEM((tile_a, npad), dt),
            pltpu.VMEM((num_heads, tile_a), dt),
            pltpu.VMEM((1, tile_a), dt),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="acq_score",
    )(anchors, train_t, linv, alphas, mask, amp2, *small)
    return out[:, 0, :]
