"""JAX's persistent compilation cache, set up in one place.

Every entry point calls :func:`enable_compile_cache` first thing in
``main``.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and no code names another directory.  Where it is not, the cache
lives at a fixed, git-ignored path inside the checkout: the path is part
of the cache key, so a directory that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory compiled programs are cached in."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Point JAX at :func:`compile_cache_dir`; returns the directory."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
