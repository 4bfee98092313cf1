"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``) call :func:`use_compile_cache` once before their
first compile; nothing calls it at import. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets
nothing. Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed
path, because the path is part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
