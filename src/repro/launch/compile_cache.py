"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``examples/serve_moe.py``) call
``enable_compile_cache()`` once, before their first compile. Tests never
call it.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is not set: a
#: fixed path, since the path is part of what a later run must find again
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this function sets nothing. Otherwise the cache goes to the
    repository's ``.jax_cache`` directory (listed in ``.gitignore``)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
