"""JAX's persistent compilation cache for the entry points.

Every SLW bucket is its own train-step executable, and every prompt bucket
its own prefill, so a cold run compiles the whole ladder before it does any
work.  The persistent cache keeps those executables on disk for the next
run.  A cache entry is found again only at the same directory, so the
directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` where it is set, otherwise
``.jax_cache/`` at the root of this checkout.

The CLIs call :func:`enable_compile_cache` first; tests never do, so a test
run leaves nothing on disk.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/compile_cache.py -> the checkout root is three levels up
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(CHECKOUT_CACHE_DIR))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
