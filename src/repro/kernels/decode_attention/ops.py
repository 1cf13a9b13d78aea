"""Public wrapper for flash-decode: model layout + padding + GQA packing."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.decode_attention.kernel import BC, decode_attention_pallas

__all__ = ["decode_attention"]


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def decode_attention(
    q: jax.Array,  # (B, Hq, Dh) — single query token per sequence
    k_cache: jax.Array,  # (B, C, Hkv, Dh)
    v_cache: jax.Array,  # (B, C, Hkv, Dh)
    valid: jax.Array,  # (B, C) bool — live cache slots
    *,
    softcap: float = 0.0,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    b, hq, dh = q.shape
    c, hkv = k_cache.shape[1], k_cache.shape[2]
    g = hq // hkv
    scale = dh**-0.5

    cpad = -(-c // BC) * BC
    dpad = max(128, -(-dh // 128) * 128)
    gpad = max(8, -(-g // 8) * 8)

    qg = q.reshape(b, hkv, g, dh).reshape(b * hkv, g, dh)
    qg = jnp.pad(qg, ((0, 0), (0, gpad - g), (0, dpad - dh)))

    def prep_cache(x):
        x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * hkv, c, dh)
        return jnp.pad(x, ((0, 0), (0, cpad - c), (0, dpad - dh)))

    kp, vp = prep_cache(k_cache), prep_cache(v_cache)
    vmask = jnp.repeat(valid[:, None, :], hkv, axis=1).reshape(b * hkv, 1, c)
    vmask = jnp.pad(vmask.astype(jnp.int32), ((0, 0), (0, 0), (0, cpad - c)))

    out = decode_attention_pallas(
        qg, kp, vp, vmask, scale=scale, softcap=softcap, interpret=interpret
    )  # (B·Hkv, gpad, dpad)
    out = out[:, :g, :dh].reshape(b, hkv, g, dh).reshape(b, hq, dh)
    return out
