"""Persistent XLA compile cache at a fixed path.

JAX keys a cached executable by its directory among other things, so the
directory never comes from a temp name, a pid or the clock.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory:
    $JAX_COMPILATION_CACHE_DIR when set (JAX reads it itself, and no
    other directory is configured), else <repo>/.jax_cache."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE)
    return REPO_CACHE
