"""Persistent XLA compilation cache for the entry points.

``enable_compile_cache()`` is called once by each launcher (``serve``,
``train``, ``peers``) and by ``chip_smoke.py``, before their first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing here overrides it.  Otherwise the cache lives at the
fixed ``<repo>/.jax_cache`` (gitignored): a later run finds what an
earlier one compiled only if the directory stays the same.  The test
suite never calls this, so tests compile without a cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "REPO_CACHE_DIR"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
