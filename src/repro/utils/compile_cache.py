"""Where JAX keeps its persistent compilation cache.

Entry points call ``enable_compile_cache()`` once at start-up; importing
this module changes nothing.  The cache directory is part of each
entry's key, so it must not move between runs: it is
``$JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise the fixed,
git-ignored ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
