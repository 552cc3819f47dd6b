"""JAX's persistent compilation cache, placed from outside or at one fixed
path inside the checkout.

A cold run on the chip pays every Mosaic and XLA compile; the persistent
cache lets processes (and later runs on the same machine) reuse them.  The
cache key includes the directory, so the path must never move: it contains
no temp name, pid or time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <repo>/.jax_cache (git-ignored): src/repro/runtime/ -> repo root
DEFAULT_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here; otherwise the cache goes to
    ``DEFAULT_DIR``."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
