"""Persistent XLA compilation cache for the entry points.

A cold run compiles one 28-layer step program per prefill and decode
bucket; the persistent cache lets the next process on the same machine
load them instead.  The cache key includes its directory, so the
directory is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when the environment
sets it (JAX reads that variable itself), otherwise ``.jax_cache`` at the
root of this checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
