"""Pallas kernels for the engine's hot spots (``acq_score``, ``matern52``)
and for the tuned model zoo's trial workloads.

Every kernel package has ``kernel.py`` (the ``pallas_call``), ``ops.py``
(padding, packing, dispatch) and ``ref.py`` (the pure-jnp oracle).
``resolve_interpret`` is the one rule all dispatchers share for whether a
kernel runs compiled or in the Pallas interpreter.
"""

from __future__ import annotations

import jax

__all__ = ["resolve_interpret"]


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Whether a Pallas kernel runs in the interpreter.

    An explicit ``interpret`` wins (compile tests lower for a described TPU
    from a CPU process). Otherwise the platform of the default device
    decides (``jax.default_device`` included, as when GPHP fitting runs on
    the host of a TPU): compiled on TPU, interpreted on CPU, and any other
    platform is refused rather than silently interpreted."""
    if interpret is not None:
        return interpret
    device = jax.config.jax_default_device
    platform = getattr(device, "platform", device) or jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on TPU or interpreted on CPU; "
        f"the default backend is {platform!r}"
    )
