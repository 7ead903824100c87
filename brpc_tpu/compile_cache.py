"""Where this repo's processes keep JAX's persistent compilation cache.

The directory is part of the cache's key, so it is decided in one place:
the caller's ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself —
nothing is set in code), else the fixed ``<repo>/.jax_cache``.  Never a
path made from a pid, a time or a temporary name: a directory that moves
never hits.
"""

from __future__ import annotations

import os
import pathlib

_REPO_CACHE = pathlib.Path(__file__).resolve().parent.parent / ".jax_cache"


def enable_compile_cache() -> str:
    """Turns the persistent cache on for this process and returns the
    directory in use.  Call before the first compilation."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        jax.config.update("jax_compilation_cache_dir", str(_REPO_CACHE))
    # The echo steps compile in well under JAX's 1 s default threshold;
    # cache them all so a second process compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return placed or str(_REPO_CACHE)
