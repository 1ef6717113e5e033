"""Where JAX keeps its persistent compilation cache.

A compiled program is cached under a key that includes the cache path, so
the directory must not move between runs: ``JAX_COMPILATION_CACHE_DIR``
when it is set (JAX reads the variable itself; nothing else is set here),
otherwise one fixed directory inside the checkout, ``<repo>/.jax_cache``
(gitignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
