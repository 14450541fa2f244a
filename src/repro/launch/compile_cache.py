"""Where compiled programs persist between runs of the entry points.

Entry points call :func:`enable_compile_cache` from their ``main()``; it
never runs at import.  A cache directory named by the environment
(``JAX_COMPILATION_CACHE_DIR``, which jax reads itself) is left alone.
Otherwise the cache is ``.jax_cache/`` at the root of the checkout: a fixed
path, because the path is part of what a cached entry is keyed on.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point jax's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
