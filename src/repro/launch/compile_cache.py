"""JAX's persistent compilation cache, kept at one fixed place.

The cache key includes its directory, so a per-run or temporary path never
hits.  Entry points that run on the chip (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once, before their
first compile; ``import repro`` does not, so the tests never write a cache.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache — this file is <checkout>/src/repro/launch/.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as it is (JAX reads
    it itself) and no other directory is set; otherwise the cache lives in
    ``.jax_cache/`` at the root of the checkout (listed in .gitignore)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    # Keep every program that took a noticeable compile, not only slow ones.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path
