"""JAX's persistent compilation cache, kept at one fixed place.

A full-width step takes tens of seconds to compile; the cache lets a later
process (the next launcher run, or ``chip_smoke.py`` after a launcher) load
it instead. The cache key includes the directory, so the path is fixed:
``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself and nothing is
overridden here), otherwise ``.jax_cache/`` at the checkout root.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]   # src/repro/launch/ -> root


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
