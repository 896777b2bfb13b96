"""JAX's persistent compilation cache, placed by the entry points.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``launch/serve.py``,
``benchmarks/run.py``) call `enable_compile_cache` once, before their
first compile; importing any library module never does. The cache's
directory is part of what makes an entry findable again, so it is never
built from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

ROOT = Path(__file__).resolve().parents[3]
# <repo>/.jax_cache — git-ignored
DEFAULT_CACHE_DIR = ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and no other directory is set here; otherwise the cache
    lives at the one fixed path `DEFAULT_CACHE_DIR`.

    Entries are keyed with the programs' op metadata: by default JAX
    leaves it out of the key, and a program that differs from a cached
    one only in its scope names (`telemetry.SCOPES`) would then run the
    cached executable, whose profiler trace shows the old names. Source
    paths in that metadata are written from the checkout's root, so an
    entry does not depend on where the checkout lies."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(f"{ROOT}{os.sep}"))
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
