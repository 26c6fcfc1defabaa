"""JAX's persistent compilation cache at a fixed path.

Call :func:`enable_compile_cache` before anything compiles. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no other
path is set. Otherwise the cache is ``<checkout>/.jax_cache``: a path that
never moves, since the path is part of what a later run must find.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
