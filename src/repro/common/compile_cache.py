"""Where JAX keeps its persistent compilation cache for this repo's entry
points.

Called from the command-line entry points and ``chip_smoke.py``, once, before
the first compile; never at import, so a library user's cache settings stand.
"""
from __future__ import annotations

import os

# <checkout>/src/repro/common/compile_cache.py -> <checkout>/.jax_cache
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
    is set here. Otherwise the cache is the fixed, git-ignored
    ``<checkout>/.jax_cache``: the directory is part of every entry's key, so
    it is never built from a temp name, a pid or a time."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
