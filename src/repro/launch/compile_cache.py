"""Persistent XLA compile cache for the programs that compile real models.

A cold run recompiles every prefill bucket and decode step; the cache keeps
them across processes.  JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so
when it is set nothing is configured here.  Otherwise the cache lives at
the fixed ``<repo>/.jax_cache`` (git-ignored): the directory is part of
the cache key, so it never comes from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
