"""Persistent JAX compilation cache, placed from outside the program.

Entry points (``chip_smoke.py`` and the benchmark ``__main__`` blocks) call
:func:`enable_compile_cache` once before their first compile; importing the
library never does, so tests run without a persistent cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
sets no other directory. Otherwise the cache is ``<checkout>/.jax_cache``:
a fixed path, because the directory is part of what a later run must find.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir() -> str:
    """The directory the compilation cache uses."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE)


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile; returns its directory."""
    import jax

    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    # the rank programs compile in well under the 1 s default threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
