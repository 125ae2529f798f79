"""Seconds to lower, compile (or load from the persistent cache) and
warm the timed call."""


def read(r):
    return r.setup.get("compile_s")
