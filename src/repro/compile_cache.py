"""JAX's persistent compilation cache, set up once per entry point.

Every entry point (``repro.launch.train``, ``repro.launch.serve``,
``benchmarks/run.py``, ``chip_smoke.py``) calls :func:`enable_compile_cache`
before it compiles. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and nothing else is set here. Otherwise the cache lives at one
fixed directory inside the checkout (``.jax_cache``, git-ignored): the
path is part of the cache key, so it must never move between runs.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

#: The in-checkout cache directory used when the environment names none.
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
