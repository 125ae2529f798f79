"""A uniform random matrix: ``nnz`` draws of a coordinate, duplicates merged."""
import numpy as np


def generate(params, seed):
    rng = np.random.default_rng(params["graph_seed"])
    n = int(params["n"])
    key = np.unique(rng.integers(0, n * n, int(params["nnz"])))
    return key // n, key % n, rng.random(key.size) + 0.5, (n, n)
