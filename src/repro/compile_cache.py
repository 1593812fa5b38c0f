"""JAX's persistent compilation cache, kept at one fixed place.

Entry points that run on the chip call :func:`enable` before their
first compile.  A later run from the same place then loads compiled
programs instead of compiling them again.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is configured here.  Otherwise the cache lives in
    ``.jax_cache/`` at the root of this checkout: a fixed path, since
    the path is part of what a cached entry is found by."""
    path = os.environ.get(ENV)
    if path:
        return path
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
