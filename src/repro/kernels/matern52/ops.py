"""Jitted public wrappers for the Matérn-5/2 Pallas gram kernel.

The inputs are warped (``repro.core.gp.warping``, which in f32 uses the
chip-accurate ``exp_accurate`` and ``log_accurate``) and lengthscale-scaled
here, by XLA in f32 — the reference's own first steps — and padded: rows to
tile multiples, features to a multiple of 8 with zeros (inert in distances).
The kernel runs in f32, compiled on a TPU and interpreted on the CPU
(``repro.kernels.resolve_interpret``; other platforms are refused); the
wrapper trims the padding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.gp.params import GPHyperParams
from repro.core.gp.warping import warp_inputs
from repro.kernels import resolve_interpret
from repro.kernels.matern52.kernel import (
    ROW_TILE,
    TILE_M,
    TILE_N,
    exp_accurate,
    matern52_gram_pallas,
)

__all__ = ["matern52_gram", "matern52_cross", "scaled_inputs"]


def _pad_to(x: jax.Array, size: int, axis: int) -> jax.Array:
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def scaled_inputs(x: jax.Array, params: GPHyperParams, warp: bool = True):
    """ω(x)/ℓ in x's dtype: the warped (``repro.core.gp.warping``),
    lengthscale-scaled coordinates whose Euclidean distances the Matérn
    kernel takes. ``params`` may carry a leading sample axis; the result
    then has it too."""
    if warp:
        x = warp_inputs(x, params.log_warp_a[..., None, :],
                        params.log_warp_b[..., None, :])
    return x * exp_accurate(-params.log_lengthscale)[..., None, :]


def _gram(x1, x2, params, warp, tile_n, interpret):
    n, d = x1.shape
    m = x2.shape[0]
    npad = -(-n // tile_n) * tile_n
    mpad = -(-m // TILE_M) * TILE_M
    dpad = max(8, -(-d // 8) * 8)
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)

    def prep(x, rows):
        s = scaled_inputs(x.astype(jnp.float32), params, warp)
        return _pad_to(_pad_to(s, rows, 0), dpad, 1)

    amp2 = exp_accurate(2.0 * params.log_amplitude)
    out = matern52_gram_pallas(
        prep(x1, npad), prep(x2, mpad).T, amp2.reshape(1, 1),
        tile_n=tile_n, interpret=resolve_interpret(interpret),
    )
    return out[:n, :m].astype(x1.dtype)


def matern52_gram(
    x1: jax.Array,
    x2: jax.Array,
    params: GPHyperParams,
    *,
    warp: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Drop-in replacement for ``matern52_ard`` (same semantics/shapes)."""
    return _gram(x1, x2, params, warp, TILE_N, interpret)


def matern52_cross(
    x_new: jax.Array,
    x_train: jax.Array,
    params: GPHyperParams,
    *,
    warp: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Cross-covariance row k(x_new, X): (d,), (m, d) -> (m,).

    The incremental append path (``repro.core.gp.incremental``) calls this
    once per new observation; only one ROW_TILE × m block is computed
    instead of an n×n gram.
    """
    return _gram(x_new[None, :], x_train, params, warp, ROW_TILE, interpret)[0]
