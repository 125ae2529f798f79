"""Chip benchmark of the CB-SpMV library: one cell, one run, one result line.

See ``chipbench/run.py`` for the command and ``BENCHMARK.json`` for the
cells. Everything the benchmark measures with (generators, references,
floor bytes, trace reduction, peaks) lives in this package, apart from
the program under test.
"""
