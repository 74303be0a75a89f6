"""JAX persistent compilation cache at a fixed place.

``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as it is (JAX reads it
itself). Otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed
path, because the directory is part of what a later run must find again.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
