"""Public wrapper for the Mamba selective-scan kernel: layout + padding."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.mamba_scan.kernel import BD, CS, mamba_scan_pallas

__all__ = ["selective_scan"]


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan(
    u: jax.Array,  # (B, S, di) f32
    dt: jax.Array,  # (B, S, di)
    a: jax.Array,  # (di, ds)
    b_t: jax.Array,  # (B, S, ds)
    c_t: jax.Array,  # (B, S, ds)
    interpret: bool | None = None,
) -> jax.Array:
    """Matches ``selective_scan_ref`` semantics: returns y (B, S, di) f32."""
    interpret = resolve_interpret(interpret)
    bsz, s, di = u.shape
    ds = a.shape[1]
    spad = -(-s // CS) * CS
    dpad = -(-di // BD) * BD

    def prep_chan(x):  # (B,S,di) -> (B, dpad, spad)
        x = jnp.transpose(x, (0, 2, 1)).astype(jnp.float32)
        return jnp.pad(x, ((0, 0), (0, dpad - di), (0, spad - s)))

    def prep_state(x):  # (B,S,ds) -> (B, ds, spad)
        x = jnp.transpose(x, (0, 2, 1)).astype(jnp.float32)
        return jnp.pad(x, ((0, 0), (0, 0), (0, spad - s)))

    up, dtp = prep_chan(u), prep_chan(dt)
    # padded channels: a = 0 ⇒ a_bar = 1, u = 0 ⇒ h stays 0 ⇒ y = 0 (trimmed)
    ap = jnp.pad(a.astype(jnp.float32), ((0, dpad - di), (0, 0)))
    bp, cp = prep_state(b_t), prep_state(c_t)
    y = mamba_scan_pallas(up, dtp, ap, bp, cp, interpret=interpret)
    return jnp.transpose(y[:, :di, :s], (0, 2, 1))
