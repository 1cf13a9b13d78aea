"""Pallas TPU kernel: Matérn-5/2 ARD gram blocks, and the in-kernel math
that ``acq_score`` shares.

The GP rebuilds K (n×m, O(n²d)) once per MCMC sample. The wrapper (ops.py)
warps and lengthscale-scales the inputs in XLA, as the reference does, and
hands the kernel f32 scaled coordinates s. Per (row tile × column tile)
the kernel computes

    k(s, s') = amp² (1 + √5 r + 5r²/3) e^{−√5 r},   r = ‖s − s'‖,

in VMEM and writes only the output tile — one HBM pass, no (n, m, d)
difference tensor.

Accuracy on the chip. On a TPU v5e the f32 ``log``/``log1p`` are off by
~3.5e-4 relative and ``exp`` by ~5e-6, in Mosaic and in XLA alike, and a
default-precision dot rounds its operands to bf16 (5e-3 of scale). So
``exp_accurate`` and ``log_accurate`` rebuild eˣ and ln x from mul/add and
exponent bits, the wrapper's warp uses them, and distances are explicit
per-feature differences on the VPU — the oracle's own form, with no
‖a‖² + ‖b‖² − 2a·b cancellation. Every constant is a weak Python float,
so nothing promotes to f64 inside the body when the process has
``jax_enable_x64`` on; in f64 (the CPU interpreter under x64) the same
code is accurate to f64.

Layout: rows (TILE_N or ROW_TILE, dpad); columns transposed (dpad, TILE_M),
lane-dense. Padded features are 0 on both sides (inert); padded rows and
columns are trimmed by the wrapper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = [
    "matern52_gram_pallas", "exp_accurate", "log_accurate", "sqdist",
    "matern52",
    "TILE_N", "TILE_M", "ROW_TILE",
]

TILE_N = 128
TILE_M = 128
ROW_TILE = 8  # f32 sublane minimum: the cross-row kernel carries 8 lhs rows
_SQRT5 = 2.2360679774997896
_I0 = np.int32(0)  # block index: int32 even when x64 is on

_LOG2E = 1.4426950408889634
_LN2_HI = 0.693145751953125  # 15 bits: k·_LN2_HI is exact for |k| ≤ 2⁸
_LN2_LO = 1.4286068203094173e-06  # ln 2 − _LN2_HI
_SQRT_HALF = 0.7071067811865476
# Taylor coefficients of eʳ, highest degree first: on |r| ≤ ln2/2 the
# degree-13 remainder is below 1e-17, so the result is f64-accurate too
_EXP_TAYLOR = tuple(1.0 / float(np.prod(np.arange(1, k + 1)))
                    for k in range(13, -1, -1))


def exp_accurate(x):
    """eˣ to the rounding of x's dtype, from mul/add/floor and a bitcast.

    x = k·ln2 + r with |r| ≤ ln2/2 (Cody–Waite split), eʳ by Horner, and
    2ᵏ built as f32 exponent bits. x is clamped to [−87, 88] so 2ᵏ stays a
    normal f32: below −87 the result is ~1.6e-38 instead of smaller."""
    x = jnp.minimum(jnp.maximum(x, -87.0), 88.0)
    k = jnp.floor(x * _LOG2E + 0.5)
    r = (x - k * _LN2_HI) - k * _LN2_LO
    p = _EXP_TAYLOR[0]
    for c in _EXP_TAYLOR[1:]:
        p = p * r + c
    bits = jax.lax.shift_left(k.astype(jnp.int32) + 127, jnp.int32(23))
    return p * jax.lax.bitcast_convert_type(bits, jnp.float32)


def log_accurate(x):
    """ln x for positive normal x, to the rounding of x's dtype.

    x = m·2ᵉ with m in [√½, √2) (``frexp``), ln m = 2·atanh(t) with
    t = (m − 1)/(m + 1), |t| ≤ 0.172, summed to t²¹ (f64-accurate).
    Lowers in XLA; the kernels' wrappers use it for the warp."""
    m, e = jnp.frexp(x)  # m in [0.5, 1)
    low = m < _SQRT_HALF
    m = jnp.where(low, 2.0 * m, m)
    e = (e - low.astype(e.dtype)).astype(x.dtype)
    t = (m - 1.0) / (m + 1.0)
    s = t * t
    p = 1.0 / 21.0
    for k in range(9, -1, -1):
        p = p * s + 1.0 / (2 * k + 1)
    return e * _LN2_HI + (e * _LN2_LO + 2.0 * t * p)


def sqdist(s1, s2t):
    """Squared distances (rows, cols) between the rows of s1 (rows, dpad)
    and the columns of s2t (dpad, cols), as explicit per-feature
    differences on the VPU."""
    r2 = None
    for f in range(s1.shape[1]):
        diff = s1[:, f : f + 1] - s2t[f : f + 1, :]
        r2 = diff * diff if r2 is None else r2 + diff * diff
    return r2


def matern52(r2, amp2):
    """Matérn-5/2 response from squared scaled distances."""
    r = jnp.sqrt(r2)
    return amp2 * (1.0 + _SQRT5 * r + (5.0 / 3.0) * r2) * exp_accurate(-_SQRT5 * r)


def _kernel(
    s1_ref,  # (tile_n, dpad) scaled rows
    s2t_ref,  # (dpad, TILE_M) scaled columns, transposed
    amp2_ref,  # (1, 1) signal variance
    out_ref,  # (tile_n, TILE_M)
):
    out_ref[...] = matern52(sqdist(s1_ref[...], s2t_ref[...]), amp2_ref[...])


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def matern52_gram_pallas(
    s1: jax.Array,  # (n_pad, dpad) f32, n_pad % tile_n == 0
    s2t: jax.Array,  # (dpad, m_pad) f32, m_pad % TILE_M == 0
    amp2: jax.Array,  # (1, 1) f32
    *,
    tile_n: int = TILE_N,
    interpret: bool = False,
) -> jax.Array:
    """Gram blocks (n_pad, m_pad). ``tile_n = ROW_TILE`` with an 8-row lhs
    is the cross-row path of the rank-1 append: one row of K costs
    (ROW_TILE + TILE_M)·d reads per tile instead of an n×n gram."""
    n, d = s1.shape
    m = s2t.shape[1]
    return pl.pallas_call(
        _kernel,
        grid=(n // tile_n, m // TILE_M),
        in_specs=[
            pl.BlockSpec((tile_n, d), lambda i, j: (i, _I0)),
            pl.BlockSpec((d, TILE_M), lambda i, j: (_I0, j)),
            pl.BlockSpec((1, 1), lambda i, j: (_I0, _I0)),
        ],
        out_specs=pl.BlockSpec((tile_n, TILE_M), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), s1.dtype),
        interpret=interpret,
        name="matern52_gram",
    )(s1, s2t, amp2)
