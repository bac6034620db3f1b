"""JAX's persistent compilation cache, kept at one fixed path.

Entry points that compile full-size programs (``chip_smoke.py``,
``repro.launch.train``, ``benchmarks.serve_bench``,
``benchmarks.stream_bench``) call :func:`enable_compile_cache` once, before
their first compile, so a second run of the same programs loads them
instead of compiling again. The cache path is part of the cache key, so it
never moves: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself),
otherwise ``.jax_cache/`` at the root of the checkout. Tests never call it.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


__all__ = ["CACHE_DIR", "enable_compile_cache"]
