"""JAX's persistent compilation cache, set up once by every entry point.

``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and JAX
reads it itself, so no other directory is set here.  Otherwise the cache
lives at ``<checkout>/.jax_cache``: a fixed path, because the path is
part of every cache key and a directory that moves never hits.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Point JAX at the persistent cache before the first compile;
    returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
