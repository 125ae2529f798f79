"""Compiles in this run that missed JAX's persistent cache, as the program
counts them (``repro.compile_cache.counts``). Nothing compiles after
warm-up, so every miss falls in set-up."""


def read(r):
    from repro import compile_cache

    counts = getattr(compile_cache, "counts", None)
    return None if counts is None else counts["misses"]
