"""JAX's persistent compilation cache, placeable from outside.

Scripts that compile the solver (``chip_smoke.py``) call
:func:`enable_compilation_cache` once at start-up; importing the library
never touches the cache.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and no other
  directory is configured here.
* otherwise the cache goes to the fixed ``<repo>/.jax_cache`` (git-ignored).
  The directory is part of the cache's key, so it never depends on a
  temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["ENV_VAR", "DEFAULT_DIR", "enable_compilation_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
