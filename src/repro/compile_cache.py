"""Where the entry points keep JAX's persistent compilation cache, and
how often it hits.

``chip_smoke.py`` and ``benchmarks/run.py`` call :func:`enable` before
their first compile, so a later process on the same checkout finds the
programs an earlier one compiled. The directory must not move between
runs, so it is never derived from a temp name, a pid or the time.

Importing this module registers one ``jax.monitoring`` listener that
counts the persistent cache's hits and misses in this process
(``counts``), and feeds them to the obs counters
``repro.compile_cache.hits`` and ``repro.compile_cache.misses``.
``kernels/ops.py`` imports it, so the counts start before the program's
first compile. A miss is a compile that wrote a new cache entry.
"""
from __future__ import annotations

import collections
import os
import pathlib

import jax

from repro import obs

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache — git-ignored.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"

_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}

# Hits and misses since this module was imported. Authoritative: they
# keep counting when obs is disabled or its registry is reset. Not an
# ``obs.MirroredCounter``: that mirrors each key as a label of a single
# metric, where the catalog names two metrics without labels.
counts: collections.Counter = collections.Counter()


def _count(event: str, **_kwargs) -> None:
    kind = _EVENTS.get(event)
    if kind is not None:
        counts[kind] += 1
        obs.counter(f"repro.compile_cache.{kind}").inc()


jax.monitoring.register_event_listener(_count)


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
