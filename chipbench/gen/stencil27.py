"""HPCG's operator: the 27-point stencil on an ``nx * ny * nz`` grid.

Row ``i + nx*(j + ny*k)`` holds ``diag`` on the diagonal and ``offdiag``
for each of the (up to 26) neighbours inside the grid, as HPCG's
``GenerateProblem`` builds it. Rows are ordered x fastest, as in HPCG.
"""
from __future__ import annotations

import numpy as np


def generate(params: dict, seed: int):
    del seed  # the operator is fixed by the grid
    nx, ny, nz = (int(params[k]) for k in ("nx", "ny", "nz"))
    n = nx * ny * nz
    idx = np.arange(n, dtype=np.int64)
    ix, iy, iz = idx % nx, (idx // nx) % ny, idx // (nx * ny)
    rows, cols, vals = [], [], []
    for dz in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                ok = ((ix + dx >= 0) & (ix + dx < nx) & (iy + dy >= 0)
                      & (iy + dy < ny) & (iz + dz >= 0) & (iz + dz < nz))
                r = idx[ok]
                rows.append(r)
                cols.append(r + dx + nx * (dy + ny * dz))
                v = params["diag"] if dx == dy == dz == 0 else params["offdiag"]
                vals.append(np.full(r.size, float(v)))
    rows = np.concatenate(rows)
    cols = np.concatenate(cols)
    vals = np.concatenate(vals)
    order = np.lexsort((cols, rows))
    return rows[order], cols[order], vals[order], (n, n)
