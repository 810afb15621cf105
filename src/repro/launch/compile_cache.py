"""JAX's persistent compilation cache, switched on by the entry points.

Only ``launch/serve.py``, ``chip_smoke.py`` and ``benchmarks/run.py``
call :func:`enable_compile_cache`, from their ``main``; importing a
module or running the tests never turns it on.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    that directory is used as is.  Otherwise the cache lives in
    ``.jax_cache/`` at the repository root: a fixed path, because the
    path is part of what a later run must find again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
