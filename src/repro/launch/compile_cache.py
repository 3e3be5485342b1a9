"""JAX's persistent compilation cache at a fixed place.

A fresh process compiles every program again; on the chip that is minutes
of a run.  :func:`enable_compile_cache` keeps compiled programs on disk so
that the next process with the same programs loads them instead.  The
cache key includes the directory, so the directory never moves: it is the
one ``JAX_COMPILATION_CACHE_DIR`` names, or else ``.jax_cache`` at the
root of this checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory.  Call before
    the first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    reads it itself and nothing is set here."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
