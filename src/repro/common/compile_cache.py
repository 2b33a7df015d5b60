"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``repro.launch.*``, ``benchmarks.*``)
calls ``enable_compile_cache`` before it compiles anything, so processes
that compile the same programs share them across runs.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# one fixed directory inside the checkout: the cache is keyed on the
# programs, so a path that moved between runs would never hit
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here; otherwise the cache lives in
    ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
