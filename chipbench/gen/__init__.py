"""Matrix generators owned by the benchmark, one module per generator.

Each module exposes ``generate(params: dict, seed: int)`` returning
``(rows, cols, vals, shape)`` as numpy arrays (int64 coordinates, float64
values) with no duplicate coordinates. The configuration file names the
module under ``generator`` and its parameters under ``params``.
"""
