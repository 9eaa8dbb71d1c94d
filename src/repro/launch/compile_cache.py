"""Persistent XLA compilation cache for the serving entry points.

Called once at start-up by ``chip_smoke.py`` and ``repro.launch.serve``,
never at import.  ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX
reads it itself); otherwise the cache lives at ``<checkout>/.jax_cache``.
The path is fixed — it is part of every entry's key, so a directory that
moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
