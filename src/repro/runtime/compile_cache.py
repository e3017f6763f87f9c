"""Where JAX keeps its persistent compilation cache.

Entry points that can reach the chip call ``enable_compile_cache()`` first;
library imports and tests never do.  A directory given from outside
(``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself) wins and nothing
else is set.  Otherwise the cache lives at the fixed ``<repo>/.jax_cache``
(git-ignored): the path is part of the cache key, so a name that moved
between runs would never hit.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory."""
    outside = os.environ.get(ENV_VAR)
    if outside:
        return outside
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
