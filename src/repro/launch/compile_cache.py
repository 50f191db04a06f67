"""JAX's persistent compilation cache for the repo's entry points.

Compiling the ring and GES programs at paper scale takes tens of seconds, and
every process starts with no compiled code.  The entry points (``chip_smoke.py``,
``launch/cges_run.py``, ``benchmarks/table2.py``) call
:func:`enable_compile_cache` once, before their first compile, so processes
that run the same programs share them.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and no other directory is set here; otherwise
the cache lives at one fixed path inside the checkout, ``<repo>/.jax_cache``
(the path is part of the cache's key, so it must not move between runs).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """Where the cache lives: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<repo>/.jax_cache``."""
    return os.environ.get(ENV_VAR) or str(REPO_ROOT / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
