"""JAX's persistent compilation cache, placed from outside or at one fixed
path.

The cache key includes the directory, so a path that moves between runs
never hits. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing here overrides it; otherwise the cache lives at
``<checkout>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
