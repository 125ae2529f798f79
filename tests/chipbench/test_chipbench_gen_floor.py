"""The benchmark's generators, floor work and peaks."""
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import floor, peaks  # noqa: E402
from chipbench.gen import kronecker, stencil27  # noqa: E402

KRON = {"scale": 9, "edgefactor": 16, "a": 0.57, "b": 0.19, "c": 0.19,
        "graph_seed": 7}


@pytest.mark.parametrize("n", [2, 3, 5, 8])
def test_stencil_has_hpcg_nnz_and_values(n):
    r, c, v, shape = stencil27.generate(
        {"nx": n, "ny": n, "nz": n, "diag": 26.0, "offdiag": -1.0}, 0)
    assert shape == (n ** 3, n ** 3)
    assert r.size == (3 * n - 2) ** 3
    assert np.all(v[r == c] == 26.0) and np.all(v[r != c] == -1.0)
    assert np.sum(r == c) == n ** 3
    A = {(a, b) for a, b in zip(r.tolist(), c.tolist())}
    assert all((b, a) in A for a, b in A)


def test_stencil_b_of_ones_is_hpcg_rhs():
    """HPCG's b = A*1: 26 - (number of neighbours) per row."""
    r, c, v, shape = stencil27.generate(
        {"nx": 4, "ny": 4, "nz": 4, "diag": 26.0, "offdiag": -1.0}, 0)
    b = np.bincount(r, weights=v, minlength=shape[0])
    assert b[0] == 26.0 - 7 and b.max() == 26.0 - 7 and b.min() == 26.0 - 26


def test_kronecker_is_symmetric_loop_free_and_deterministic():
    r, c, v, shape = kronecker.generate(KRON, 7)
    assert shape == (512, 512)
    assert not np.any(r == c)
    key = r * shape[1] + c
    assert np.unique(key).size == key.size
    assert np.array_equal(np.sort(key), np.sort(c * shape[1] + r))
    # The graph follows graph_seed alone, whatever the run's seed.
    r2, c2, v2, _ = kronecker.generate(KRON, 8)
    assert np.array_equal(r, r2) and np.array_equal(c, c2) and np.array_equal(v, v2)
    r3, c3, _, _ = kronecker.generate(dict(KRON, graph_seed=8), 7)
    assert r3.size != r.size or not np.array_equal(c, c3)


def test_kronecker_values_are_a_transition_matrix():
    r, c, v, shape = kronecker.generate(KRON, 3)
    colsum = np.bincount(c, weights=v, minlength=shape[1])
    used = np.bincount(c, minlength=shape[1]) > 0
    np.testing.assert_allclose(colsum[used], 1.0)


def test_kronecker_is_skewed():
    r, _, _, shape = kronecker.generate(dict(KRON, scale=12), 0)
    deg = np.bincount(r, minlength=shape[0])
    assert deg.max() > 20 * deg[deg > 0].mean()


def test_spmv_floor():
    rows = np.array([0, 0, 1, 3])
    cols = np.array([0, 2, 2, 1])
    counts = floor.matrix_counts(rows, cols, (5, 4))
    assert counts == {"nnz": 4, "m": 5, "cols_used": 3}
    assert floor.spmv(counts, 4) == (4.0 * (4 + 3 + 5), 8.0)


def test_cg_floor():
    counts = {"nnz": 100, "m": 32, "cols_used": 32}
    b, f = floor.cg_iteration(counts, 4, 16)
    assert b == 4.0 * (100 + 2 * 256 + 5 * 32)
    assert f == 2.0 * 100 + 2 * 2 * 256 + 12 * 32


def _matrices():
    yield "kronecker", kronecker.generate(KRON, 1)
    yield "stencil", stencil27.generate(
        {"nx": 8, "ny": 8, "nz": 8, "diag": 26.0, "offdiag": -1.0}, 0)


@pytest.mark.parametrize("B", [8, 16, 24])
def test_floor_bytes_at_most_the_programs_argument_bytes(B):
    """No layout the program builds moves fewer bytes than the floor."""
    import jax

    from repro.core import CBMatrix
    from repro.core.streams import build_super_streams

    for _, (r, c, v, shape) in _matrices():
        cb = CBMatrix.from_coo(r, c, v.astype(np.float32), shape, block_size=B)
        streams = build_super_streams(cb)
        arg = sum(a.nbytes for a in jax.tree_util.tree_leaves(streams))
        arg += 4 * (shape[0] + shape[1])  # x read and y written
        fb, _ = floor.spmv(floor.matrix_counts(r, c, shape), 4)
        assert fb <= arg


def test_peaks_table():
    p = peaks.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["flops_per_s"] == 197e12
    assert p["hbm_bytes"] == 16e9 and "TPU v5e" in p["source"]
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")


def test_least_time_takes_the_binding_bound():
    assert peaks.least_time_s(819e9, 1.0, "TPU v5 lite", 1) == pytest.approx(1.0)
    assert peaks.least_time_s(1.0, 4 * 197e12, "TPU v5 lite", 4) == pytest.approx(1.0)


def test_config_files_hold_what_the_generators_read():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["dtype"] == "float32" and cfg["assumed"]
