"""Persistent XLA compilation cache for the entry points.

Called by the entry points (``chip_smoke.py``, ``benchmarks/run.py``,
the examples), never at import: a library import must not move a
caller's cache.
"""

from __future__ import annotations

import os

import jax

_ENV = "JAX_COMPILATION_CACHE_DIR"
#: The checkout's own cache directory (listed in ``.gitignore``). Fixed,
#: because the directory is part of the cache key: one that moves
#: between runs never hits.
DEFAULT_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache")
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    env = os.environ.get(_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
