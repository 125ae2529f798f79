"""Graph500 Kronecker graph (the reference generator's recursive R-MAT form).

Each of ``edgefactor * 2**scale`` edges picks one quadrant per bit with the
initiator probabilities A, B, C (D = 1 - A - B - C), as the Graph500
reference code does. Vertex labels are then permuted at random. The edge
list is symmetrised; duplicate edges and self-loops are removed. Values are
``1 / out-degree`` of the source vertex, so ``A[i, j] = 1 / deg(j)``: the
column-stochastic PageRank transition matrix of the graph.

The graph is drawn from ``params["graph_seed"]``, never from the run's
seed: one fixed data set, as a Graphalytics data set is one file.
"""
from __future__ import annotations

import numpy as np


def edges(scale: int, edgefactor: int, a: float, b: float, c: float,
          rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
    """The raw (directed, possibly duplicated) Kronecker edge list."""
    n_edges = edgefactor << scale
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    src = np.zeros(n_edges, np.int64)
    dst = np.zeros(n_edges, np.int64)
    for bit in range(scale):
        src_bit = rng.random(n_edges, dtype=np.float32) > ab
        thresh = np.where(src_bit, c_norm, a_norm).astype(np.float32)
        dst_bit = rng.random(n_edges, dtype=np.float32) > thresh
        src |= src_bit.astype(np.int64) << bit
        dst |= dst_bit.astype(np.int64) << bit
    perm = rng.permutation(1 << scale)
    return perm[src], perm[dst]


def generate(params: dict, seed: int):
    scale = int(params["scale"])
    n = 1 << scale
    rng = np.random.default_rng(params["graph_seed"])
    src, dst = edges(scale, int(params["edgefactor"]), float(params["a"]),
                     float(params["b"]), float(params["c"]), rng)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    key = np.unique(np.concatenate([src * n + dst, dst * n + src]))
    rows, cols = key // n, key % n
    deg = np.bincount(cols, minlength=n).astype(np.float64)
    vals = 1.0 / deg[cols]
    return rows, cols, vals, (n, n)
