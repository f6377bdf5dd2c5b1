"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``benchmarks/*``, ``examples/*``)
calls ``enable_compile_cache()`` before its first compile:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads that directory itself,
    and nothing is set in code;
  * not set: the cache goes to ``.jax_cache/`` at the root of the
    checkout (listed in ``.gitignore``).  The path is part of the cache's
    key, so it is fixed: never a temporary name, a process id or a time.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    import jax  # noqa: PLC0415 - keep importing this module JAX-free
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
