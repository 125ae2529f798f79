"""Floor bytes and operations of one operation, from the matrix alone.

The floor is what any implementation must move and compute, whatever its
layout: every stored value read once, every needed entry of x read once,
every entry of y written once, at the configuration's value width. Index
bytes are left out, since a format may need none. Nothing here reads the
streams the program builds, so a later change of layout cannot move the
floor, and a share of it cannot pass 100%.
"""
from __future__ import annotations

import numpy as np


def matrix_counts(rows: np.ndarray, cols: np.ndarray, shape) -> dict:
    """nnz, rows and the columns that hold a nonzero (x entries of other
    columns are never needed)."""
    return {"nnz": int(rows.size), "m": int(shape[0]),
            "cols_used": int(np.unique(cols).size)}


def spmv(counts: dict, itemsize: int) -> tuple[float, float]:
    """(bytes, flops) of y = A @ x."""
    c = counts
    return (float(itemsize * (c["nnz"] + c["cols_used"] + c["m"])),
            float(2 * c["nnz"]))


def cg_iteration(counts: dict, itemsize: int, block_size: int) -> tuple[float, float]:
    """(bytes, flops) of one block-Jacobi-preconditioned CG iteration.

    q = A p; alpha = rz / (p.q); x += alpha p; r -= alpha q; z = M r;
    rz' = r.z; p = z + beta p; ||r||. The five vectors p, q, x, r, z are
    each counted once, and the inverse diagonal blocks of M are read once.
    """
    c = counts
    n, B = c["m"], block_size
    mb = -(-n // B)
    bytes_ = itemsize * (c["nnz"] + mb * B * B + 5 * n)
    flops = 2 * c["nnz"] + 2 * mb * B * B + 12 * n
    return float(bytes_), float(flops)
