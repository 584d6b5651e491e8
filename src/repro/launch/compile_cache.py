"""JAX's persistent compilation cache, kept at a fixed path.

Warmup compiles one program per (bucket, batch class, path, mode), and
at the paper's widths each takes seconds on a TPU. With the persistent
cache on, a process finds the programs an earlier process compiled and
loads them instead. The cache key includes its directory, so the
directory must not move between runs: never a temp name, a process id
or a time.

Entry points call :func:`enable_compile_cache` before their first
compile; library code and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed. Otherwise the cache goes to
    ``DEFAULT_CACHE_DIR``, inside the checkout and listed in
    ``.gitignore``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
