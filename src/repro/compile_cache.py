"""Where the entry points keep JAX's persistent compilation cache.

``chip_smoke.py`` and ``benchmarks/run.py`` call :func:`enable` before
their first compile, so a later process on the same checkout finds the
programs an earlier one compiled. The directory must not move between
runs, so it is never derived from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache — git-ignored.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn on the persistent cache and return the directory it uses.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and this sets no other directory. Otherwise the cache goes to the
    fixed in-checkout ``DEFAULT_DIR``. Call before the first compile:
    JAX fixes the cache's location when it first compiles.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
