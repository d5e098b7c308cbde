"""JAX's persistent compilation cache, placed for the entry points.

The entry points (``chip_smoke.py``, ``benchmarks/run.py``,
``repro.launch.serve`` and ``repro.launch.train``) call
:func:`enable_compile_cache` once at start-up; importing the library never
touches the cache.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and nothing else is set here.  Otherwise the cache lives at the fixed
``<checkout>/.jax_cache``: the path is part of what makes an entry found again,
so it never depends on a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["ENV_VAR", "CHECKOUT_CACHE", "enable_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/utils/cache.py -> the checkout root
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
