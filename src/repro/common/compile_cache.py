"""Where JAX keeps its persistent compilation cache.

Entry points call ``enable_compile_cache()`` from their ``main()`` —
never at import time — so one run's compiled programs are found again by
the next run on the same machine.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, inside the checkout: the path is part of the cache key, so a
# directory that moves between runs (a temp dir, a pid) never hits
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Use ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
    itself; nothing else is set), else the fixed ``<repo>/.jax_cache``.
    Returns the directory in use."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
