"""JAX's persistent compilation cache, placed from outside or at one fixed
path inside the checkout.

Every entry point calls :func:`enable_compile_cache` once at start-up;
nothing here runs at import time.
"""
from __future__ import annotations

import os

# <checkout>/.jax_cache — listed in .gitignore; the path is part of each
# entry's key, so it must not move between runs
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it at
    start-up, and this sets nothing.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
