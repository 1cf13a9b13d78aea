"""Where entry points keep JAX's persistent compilation cache.

The engine compiles one program per (n-bucket, d, S) shape, and a cold TPU
compile of the GPHP slice sampler takes minutes, so the entry points that
start the engine on a chip (``chip_smoke.py``, the engine-server CLI,
``benchmarks/run.py``) turn the persistent cache on. Importing the library
does not: tests and library users keep whatever JAX is configured with.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

import jax

__all__ = ["CACHE_DIR_NAME", "enable_persistent_cache", "stable_source_locations"]

CACHE_DIR_NAME = ".jax_cache"  # git-ignored, inside the checkout


def stable_source_locations(checkout: Path) -> None:
    """Make the source locations JAX writes into a Pallas kernel's Mosaic
    payload independent of where the checkout lies and of who called the
    kernel: one frame per op, with the checkout's prefix removed. The
    persistent cache's key hashes that payload, so otherwise every program
    holding a kernel misses when the checkout moves or another script
    drives it."""
    prefix = re.escape(str(Path(checkout).resolve()) + os.sep)
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    jax.config.update("jax_hlo_source_file_canonicalization_regex", f"^{prefix}")


def enable_persistent_cache(checkout: Path) -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache and nothing else is
    set (JAX reads the variable itself). Otherwise the cache is the fixed
    path ``<checkout>/.jax_cache`` — fixed, because the path is part of what
    a later run must find again. Either way the kernels' source locations
    are made stable (``stable_source_locations``)."""
    stable_source_locations(checkout)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(checkout) / CACHE_DIR_NAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
