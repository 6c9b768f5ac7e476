"""Where JAX keeps its persistent compilation cache.

Only entry points call :func:`place_compile_cache` (``chip_smoke.py``,
``repro.launch.serve.main`` and ``benchmarks/run.py``'s ``main``): importing
a module never changes JAX's configuration, and tests never call it.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT_CACHE", "place_compile_cache"]

#: ``<checkout>/.jax_cache``, derived from this file's location only, so the
#: path (part of every cache key) is the same in every run of a checkout.
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing; otherwise the cache goes to :data:`CHECKOUT_CACHE`.
    Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
