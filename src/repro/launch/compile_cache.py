"""JAX's persistent compilation cache, kept at one fixed place per checkout.

The cache key includes the cache path, so a directory that moves between
runs never hits: the path is fixed, never derived from a temporary name, a
process id or the time.
"""

from __future__ import annotations

import os
import pathlib

#: the checkout's own cache directory (listed in .gitignore)
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is configured.  Otherwise the cache is
    ``<checkout>/.jax_cache``.  Call before the first compilation.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
