"""JAX's persistent compilation cache, switched on by the entry points.

``enable_compile_cache()`` is called by the runnable entry points
(``chip_smoke.py``, ``benchmarks/run.py``, ``examples/*.py``) — never at
import time and never by the tests. Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX already reads it and the cache stays there; otherwise the cache
goes to a fixed directory inside the checkout (``.jax_cache/``, listed in
``.gitignore``). The path is part of the cache's key, so it never comes
from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
