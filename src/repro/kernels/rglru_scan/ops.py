"""Public wrapper for the RG-LRU scan kernel: layout + padding."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.rglru_scan.kernel import BD, CS, rglru_scan_pallas

__all__ = ["rglru_scan"]


@functools.partial(jax.jit, static_argnames=("interpret",))
def rglru_scan(
    a: jax.Array,  # (B, S, di) f32
    gated: jax.Array,  # (B, S, di) f32
    interpret: bool | None = None,
) -> jax.Array:
    """Matches ``rglru_scan_ref``: h (B, S, di) f32."""
    interpret = resolve_interpret(interpret)
    bsz, s, di = a.shape
    spad = -(-s // CS) * CS
    dpad = -(-di // BD) * BD

    def prep(x):
        x = jnp.transpose(x, (0, 2, 1)).astype(jnp.float32)
        return jnp.pad(x, ((0, 0), (0, dpad - di), (0, spad - s)))

    h = rglru_scan_pallas(prep(a), prep(gated), interpret=interpret)
    return jnp.transpose(h[:, :di, :s], (0, 2, 1))
