"""JAX's persistent compile cache, placed once by each entry point.

The cache key includes the directory, so the directory never moves: it is
``JAX_COMPILATION_CACHE_DIR`` where that is set, else ``<repo>/.jax_cache``.
Library modules never call this; ``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve`` and ``python -m repro`` do, before they compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so where it is set this
    changes nothing.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
