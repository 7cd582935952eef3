"""JAX's persistent compilation cache for the repo's entry points.

Scripts call :func:`enable_compile_cache` first thing in ``main``; importing
the library never touches the cache, so tests compile as they always did.
"""
from __future__ import annotations

import os

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

# fixed, inside the checkout (and listed in .gitignore): a cache directory
# that moves between runs never hits, so no temp-, pid- or time-based path
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; -> the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
    nothing is set here; otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
