"""JAX persistent compilation cache for the repo's entry points.

Called from ``main`` of ``chip_smoke.py`` and ``benchmarks/run.py``,
never at import.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins (JAX
reads it itself, and no other directory is set here); otherwise the
cache lives at a fixed path inside the checkout, since the path is part
of the cache key and a moving directory never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
