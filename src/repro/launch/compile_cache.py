"""Where JAX keeps its persistent compilation cache.

`enable_compile_cache()` is called by the entry points (the solve
launcher, `chip_smoke.py`, the benchmark harness), never at import time.
When `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing
is set here; otherwise the cache goes to `<repo>/.jax_cache`.  The path
is part of what a cache entry is found by, so it is fixed: a directory
named after a pid, a time or a temp dir would never hit again.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed place and
    return the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
