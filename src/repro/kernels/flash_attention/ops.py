"""Public wrapper for the flash-attention Pallas kernel.

Accepts the model layout q (B, S, Hq, Dh), k/v (B, S, Hkv, Dh), handles
GQA via index-map arithmetic (kv tiles are *addressed*, never expanded),
pads S to block multiples (padded keys are hidden by the causal mask) and
Dh to the 128-lane width (zero-padded features are inert), then trims.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.flash_attention.kernel import BK, BQ, _kernel

__all__ = ["flash_attention"]


@functools.partial(
    jax.jit, static_argnames=("window", "softcap", "causal", "interpret")
)
def flash_attention(
    q: jax.Array,  # (B, S, Hq, Dh)
    k: jax.Array,  # (B, S, Hkv, Dh)
    v: jax.Array,
    *,
    window: int = 0,
    softcap: float = 0.0,
    causal: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    scale = dh**-0.5

    spad = -(-s // max(BQ, BK)) * max(BQ, BK)
    dpad = max(128, -(-dh // 128) * 128)

    def prep(x, heads):
        x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * heads, s, dh)
        x = jnp.pad(x, ((0, 0), (0, spad - s), (0, dpad - dh)))
        return x

    qp, kp, vp = prep(q, hq), prep(k, hkv), prep(v, hkv)
    nq, nk = spad // BQ, spad // BK
    kern = functools.partial(
        _kernel, scale=scale, window=window, softcap=softcap, causal=causal,
        num_kv_blocks=nk,
    )

    def kv_row(bh):
        return (bh // hq) * hkv + (bh % hq) // g

    out = pl.pallas_call(
        kern,
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, BQ, dpad), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, BK, dpad), lambda bh, i, j: (kv_row(bh), j, 0)),
            pl.BlockSpec((1, BK, dpad), lambda bh, i, j: (kv_row(bh), j, 0)),
        ],
        out_specs=pl.BlockSpec((1, BQ, dpad), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, spad, dpad), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((BQ, 1), jnp.float32),
            pltpu.VMEM((BQ, 1), jnp.float32),
            pltpu.VMEM((BQ, dpad), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    out = out[:, :s, :dh].reshape(b, hq, s, dh)
    return jnp.transpose(out, (0, 2, 1, 3))
