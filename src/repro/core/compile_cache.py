"""Where JAX keeps its persistent compilation cache for this checkout.

A directory that moves between runs is a new, empty cache every time, so
the default is one fixed path inside the checkout, never a temporary, pid-
or time-derived one.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["configure_compile_cache", "CHECKOUT_CACHE_DIR"]

# <checkout>/.jax_cache (this file is <checkout>/src/repro/core/...)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return it: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads that
    variable itself, so nothing is set), else ``CHECKOUT_CACHE_DIR``.
    Call it from entry points before the first compile.

    JAX's own threshold stays: programs that compile in under a second
    are not written.  A device fit compiles about a thousand of those, and
    writing them all cost a TPU v5e host more than their compiles did."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
