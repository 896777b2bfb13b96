"""JAX's persistent compilation cache, placed by the entry points.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py``,
``benchmarks/run.py``) call `enable_compile_cache` once, before their
first compile; importing any library module never does. The cache's
directory is part of what makes an entry findable again, so it is never
built from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache — git-ignored
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and no other directory is set here; otherwise the cache
    lives at the one fixed path `DEFAULT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
