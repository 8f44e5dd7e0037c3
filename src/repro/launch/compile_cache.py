"""JAX's persistent compilation cache for the entry points.

Called from ``main`` of an entry point, never at import: tests and
worker processes import these modules and must not have a cache turned
on behind their back.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: Default cache location: fixed and inside the checkout (git-ignored).
#: The directory is part of what a later run has to find again, so it is
#: never built from a temp name, a pid or the time.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here; otherwise the cache goes to
    :data:`REPO_CACHE_DIR`.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
