"""JAX's persistent compilation cache for the repo's chip entry points.

The cache directory is part of every entry's key, so it never moves: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX keeps the cache there and nothing
here overrides it; otherwise it lives at ``<checkout>/.jax_cache``.  Every
compile is kept, however short — the store's kernels compile in about a
second each, under JAX's default threshold.
"""
from __future__ import annotations

import os

import jax


def enable(checkout: str) -> str:
    """Turn the cache on before the first compile; returns its directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(checkout, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir
