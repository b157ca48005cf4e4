"""Where JAX keeps its persistent compilation cache.

Entry points that compile the planner at full size (``chip_smoke.py``,
``benchmarks/run.py``) call ``enable_compile_cache()`` before their first
compile.  The directory is part of each entry's key, so it must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX
reads it itself, so nothing is set in code), and otherwise the cache
lives at one fixed, git-ignored directory inside the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The fixed in-checkout default (``src/repro/runtime`` -> checkout root).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    ``CHECKOUT_CACHE_DIR``."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    and return it.  Call before the process compiles anything."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = ["CHECKOUT_CACHE_DIR", "ENV_VAR", "compile_cache_dir",
           "enable_compile_cache"]
