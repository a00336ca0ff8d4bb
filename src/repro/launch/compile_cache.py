"""JAX's persistent compilation cache, kept at one fixed place.

The cache key includes the directory, so a cache that moves between runs
never hits: the path comes from the environment or from the checkout, never
from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (gitignored); used when the environment names none
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to :data:`DEFAULT_DIR`.
    Call it from an entry point, before the first compilation.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
