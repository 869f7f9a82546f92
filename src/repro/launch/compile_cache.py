"""JAX's persistent compilation cache, placed from outside the program.

A run on a fresh machine compiles every program cold; the persistent cache
lets later processes on the same disk skip that. The directory is decided
once, before the first compile, by `configure_compile_cache`.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# Fixed, never derived from a temporary name, a pid or the time: a cache
# entry is only found again by a process that looks in the same directory.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Enable the persistent compilation cache; return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX has already read it and
    nothing else is set here. Otherwise the cache goes to `<repo>/.jax_cache`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
