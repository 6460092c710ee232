"""JAX's persistent compilation cache, placed once per process.

``enable_compile_cache()`` is called by every command-line entry point
(``chip_smoke.py``, ``repro.launch.solver_serve``, ``benchmarks/run.py``)
before anything compiles:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and nothing
    else is configured here;
  * unset: the cache goes to ``<checkout>/.jax_cache`` — a fixed path
    (ignored by git), because the directory is part of every entry's
    key, so a path that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def default_cache_dir() -> Path:
    return CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory;
    returns the directory in use."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax

    path = str(default_cache_dir())
    jax.config.update("jax_compilation_cache_dir", path)
    return path
