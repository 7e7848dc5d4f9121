"""JAX's persistent compilation cache, placed for an entry point.

Entry points (``chip_smoke.py``, ``repro.launch.train``, ``benchmarks.run``,
``benchmarks.bench_engine``) call :func:`enable_compile_cache` once, before
their first compile; nothing calls it on import, and the tests never do.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and that
directory is used as it is: no other is set in code.  Otherwise the cache
lives at one fixed, git-ignored directory of the checkout — a fixed path
is part of what lets a later run hit it.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
