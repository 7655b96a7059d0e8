"""Where the entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
nothing here overrides it.  Otherwise the cache goes to one fixed
directory inside the checkout, so every run of the same checkout finds
what an earlier run compiled.  The path is never derived from a temporary
name, a pid or the time: a cache whose directory moves never hits.
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path

import jax
from jax.experimental.compilation_cache import compilation_cache

REPO_ROOT = Path(__file__).resolve().parents[3]
CHECKOUT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


@contextlib.contextmanager
def persistent_cache_disabled():
    """Compile whatever runs inside without the persistent cache: nothing
    is read from it or written to it.  The cache's directory is kept and
    is used again on exit."""
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
