"""Where JAX keeps its persistent compilation cache.

When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it. Otherwise the cache goes to one fixed directory inside
the checkout, ``<repo>/.jax_cache`` (git-ignored): a later process finds its
compiled programs again only if the path does not move, so it never
depends on a temporary name, a pid or the time. No other code of the repo
sets a cache directory.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use. Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
