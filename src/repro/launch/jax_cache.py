"""Persistent compilation cache at a fixed place.

``use_compile_cache()`` leaves a ``JAX_COMPILATION_CACHE_DIR`` from the
environment to JAX.  Without one it points JAX at ``<repo>/.jax_cache``:
the cache key includes the path, so the directory never moves.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
