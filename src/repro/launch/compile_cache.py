"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points call :func:`enable_compile_cache` once, before their first
compile; no module calls it on import, so tests and library users keep
whatever cache setting they already have.

A warm start should compile nothing, wherever the checkout sits. Three of
JAX's defaults stand in the way, and the cache turns each off:

* every program's key hashes its compile options, and by default those name
  a directory inside the cache (``xla_gpu_per_fusion_autotune_cache_dir``,
  an XLA cache for GPUs), so every key depends on the cache's path;
* a key hashes the program with its debug information stripped, but a
  Pallas TPU kernel sits inside it as Mosaic bytecode serialized with its
  own, so a program that holds a kernel carries the kernel's source paths:
  the paths inside this checkout are lowered relative to it;
* a program that compiles in under a second is not written to the cache,
  so each start compiled its small programs again.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

# the checkout this module belongs to, and its default cache directory
ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = ROOT / ".jax_cache"


def source_prefix_regex(root: Path = ROOT) -> str:
    """The pattern that cuts ``<root>/`` from the front of a source path."""
    return "^" + re.escape(f"{root}{os.sep}")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the
    cache stays there; otherwise it goes to ``<repo>/.jax_cache/``. Every
    program is cached, under a key that does not depend on where the cache
    or the checkout sits.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "")
    jax.config.update("jax_hlo_source_file_canonicalization_regex", source_prefix_regex())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
