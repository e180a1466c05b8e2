"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points call :func:`enable_compile_cache` once, before their first
compile; no module calls it on import, so tests and library users keep
whatever cache setting they already have.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache: the path is part of the cache key, so it must not move
# between runs (no temp names, pids or times); the directory is gitignored.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the
    cache stays there; otherwise it goes to ``<repo>/.jax_cache/``.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
