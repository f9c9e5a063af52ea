"""Persistent XLA compilation cache for the entry points.

A 128^3 Newton step takes the better part of a minute to compile; with the
cache on, a second process (or a second run of the same entry point)
loads the compiled program instead.  The cache key includes the directory,
so the directory is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself), otherwise
``.jax_cache/`` at the root of this checkout — never a temp name.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  Call it at
    the top of an entry point's ``main()``, never at import."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
