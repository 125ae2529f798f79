"""Core CB-SpMV pipeline: unit + property tests (proptest harness).

Invariants under test (the paper's §3 claims as executable properties):
  * blocking partitions losslessly (CB round-trips to the dense matrix)
  * packed coordinates decode to the originals (Alg. 3 bit layout)
  * virtual-pointer regions are aligned and non-overlapping (Fig. 7b)
  * column aggregation preserves the matrix under restore_cols (Fig. 6b)
  * format selection respects th1/th2 (§3.3.2)
  * pq balance: equal slot count per group, near-optimal nnz spread (Alg. 2)
"""
import numpy as np
import pytest
from proptest import composite, forall, floats, integers, lists, sampled_from

from repro.core import (
    CBMatrix, FMT_COO, FMT_CSR, FMT_DENSE, FormatThresholds,
    aggregate_blocks, apply_balance, column_aggregate, partition_coo,
    select_formats, tb_load_balance,
)
from repro.core.aggregation import (
    coord_dtype, decode_coords, encode_coords, pack_block, unpack_block,
)
from repro.core.spmv_ref import dense_oracle, spmm_ref, spmv_ref
from repro.data import matrices


# ---------------------------------------------------------------------------
# strategies
# ---------------------------------------------------------------------------

@composite
def coo_matrices(draw):
    m = draw(integers(8, 120))
    n = draw(integers(8, 120))
    nnz = draw(integers(1, 200))
    rows = draw(lists(integers(0, m - 1), min_size=nnz, max_size=nnz))
    cols = draw(lists(integers(0, n - 1), min_size=nnz, max_size=nnz))
    vals = draw(lists(floats(-100, 100), min_size=nnz, max_size=nnz))
    return (np.asarray(rows), np.asarray(cols),
            np.asarray(vals, np.float32), (m, n))


# ---------------------------------------------------------------------------
# blocking
# ---------------------------------------------------------------------------

@forall(coo_matrices(), sampled_from([4, 8, 16]), examples=50)
def test_partition_roundtrip(mat, B):
    rows, cols, vals, shape = mat
    part = partition_coo(rows, cols, vals, shape, B)
    dense = np.zeros(shape, np.float32)
    np.add.at(dense, (rows, cols), vals)
    rebuilt = np.zeros(shape, np.float32)
    for i in range(part.num_blocks):
        r, c, v = part.block_elems(i)
        rebuilt[part.blk_row_idx[i] * B + r, part.blk_col_idx[i] * B + c] += v
    np.testing.assert_allclose(rebuilt, dense, rtol=1e-5, atol=1e-5)


@forall(coo_matrices(), sampled_from([8, 16]), examples=50)
def test_partition_intra_block_row_major(mat, B):
    rows, cols, vals, shape = mat
    part = partition_coo(rows, cols, vals, shape, B)
    for i in range(part.num_blocks):
        r, c, _ = part.block_elems(i)
        keys = r.astype(np.int64) * B + c
        assert np.all(np.diff(keys) > 0), "block elems must be row-major unique"


# ---------------------------------------------------------------------------
# packed coordinates + VP aggregation
# ---------------------------------------------------------------------------

@forall(sampled_from([4, 8, 16]), integers(1, 64), integers(0, 2**31),
        examples=50)
def test_coord_pack_roundtrip(B, nnz, seed):
    rng = np.random.default_rng(seed)
    r = rng.integers(0, B, nnz).astype(np.int32)
    c = rng.integers(0, B, nnz).astype(np.int32)
    packed = encode_coords(r, c, B)
    assert packed.dtype == coord_dtype(B)
    r2, c2 = decode_coords(packed, B)
    np.testing.assert_array_equal(r, r2)
    np.testing.assert_array_equal(c, c2)


@pytest.mark.parametrize("fmt", [FMT_COO, FMT_CSR, FMT_DENSE])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_pack_unpack_block(fmt, dtype):
    rng = np.random.default_rng(0)
    B = 16
    nnz = 40
    flat = rng.choice(B * B, nnz, replace=False)
    flat.sort()
    r, c = (flat // B).astype(np.int32), (flat % B).astype(np.int32)
    v = rng.standard_normal(nnz).astype(dtype)
    blob = pack_block(fmt, r, c, v, B)
    buf = np.concatenate([np.zeros(8, np.uint8), blob])  # offset region
    r2, c2, v2 = unpack_block(buf, 8, fmt, nnz, B, np.dtype(dtype))
    order = np.argsort(r * B + c)
    order2 = np.argsort(r2 * B + c2)
    np.testing.assert_array_equal(r[order], r2[order2])
    np.testing.assert_array_equal(c[order], c2[order2])
    np.testing.assert_allclose(v[order], v2[order2], rtol=1e-6)


def test_vp_alignment_and_disjointness():
    rng = np.random.default_rng(1)
    B = 16
    fmts, elems = [], []
    for i in range(20):
        nnz = int(rng.integers(1, B * B))
        flat = rng.choice(B * B, nnz, replace=False)
        flat.sort()
        r, c = (flat // B).astype(np.int32), (flat % B).astype(np.int32)
        v = rng.standard_normal(nnz).astype(np.float32)
        fmts.append(int(select_formats(np.array([nnz]), B)[0]))
        elems.append((r, c, v))
    packed = aggregate_blocks(np.asarray(fmts), elems, B, np.dtype(np.float32))
    ends = packed.vp_per_blk + packed.nbytes_per_blk
    # aligned starts, disjoint monotone regions
    assert np.all(packed.vp_per_blk % 4 == 0)
    assert np.all(packed.vp_per_blk[1:] >= ends[:-1])
    assert ends[-1] <= len(packed.packed)


# ---------------------------------------------------------------------------
# column aggregation
# ---------------------------------------------------------------------------

@forall(coo_matrices(), sampled_from([8, 16]), examples=40)
def test_column_aggregation_preserves_matrix(mat, B):
    rows, cols, vals, shape = mat
    agg = column_aggregate(rows, cols, shape, B)
    # every element's compacted column restores to its original column
    for i in range(len(rows)):
        panel = rows[i] // B
        assert agg.original_col(panel, int(agg.new_cols[i])) == cols[i]


@forall(coo_matrices(), sampled_from([8, 16]), examples=40)
def test_column_aggregation_compacts(mat, B):
    rows, cols, vals, shape = mat
    agg = column_aggregate(rows, cols, shape, B)
    # compacted width = number of distinct columns per panel
    for p in range(agg.num_panels):
        in_panel = (rows // B) == p
        expected = len(np.unique(cols[in_panel])) if in_panel.any() else 0
        assert agg.panel_width[p] == expected


# ---------------------------------------------------------------------------
# format selection + load balance
# ---------------------------------------------------------------------------

def test_format_thresholds_paper_values():
    th1, th2 = FormatThresholds().resolve(16)
    assert (th1, th2) == (32, 128)  # the paper's th1/th2 at B=16
    nnz = np.array([1, 31, 32, 128, 129, 256])
    fmt = select_formats(nnz, 16)
    assert list(fmt) == [FMT_COO, FMT_COO, FMT_CSR, FMT_CSR, FMT_DENSE, FMT_DENSE]


@forall(lists(integers(1, 256), min_size=1, max_size=300),
        sampled_from([4, 8]), examples=40)
def test_tb_balance_invariants(nnzs, warps):
    nnz = np.asarray(nnzs)
    res = tb_load_balance(nnz, warps_per_tb=warps)
    slots = res.slots
    real = slots[slots >= 0]
    # every block placed exactly once
    assert sorted(real.tolist()) == list(range(len(nnz)))
    # group loads match slot assignment
    loads = np.zeros(res.num_groups, np.int64)
    for g in range(res.num_groups):
        s = slots[g * warps : (g + 1) * warps]
        loads[g] = nnz[s[s >= 0]].sum()
    np.testing.assert_array_equal(loads, res.group_loads)
    # near-optimal: max load <= optimal + max single block (greedy LPT bound)
    assert res.group_loads.max() <= nnz.sum() / res.num_groups + nnz.max()


def test_balance_beats_naive_on_powerlaw():
    r, c, v = matrices.power_law(512, 512, seed=3)
    part = partition_coo(r, c, v, (512, 512), 16)
    from repro.core.balance import tb_load_stddev
    naive, balanced = tb_load_stddev(part.nnz_per_blk)
    assert balanced <= naive  # Fig. 4 claim


def test_lane_layout_matches_per_group_loop():
    """Packed width W and lane offsets equal the per-group Python loop
    they replaced, on a ragged width list with empty trailing slots."""
    from repro.core.balance import grid_group_balance
    from repro.core.streams import even_group, lane_layout

    widths = (np.random.default_rng(0).integers(1, 40, 37) * 8).tolist()
    _, Gs = even_group(len(widths), 8)
    bal = grid_group_balance(np.asarray(widths, np.int64), Gs)
    slot_map = bal.slots.reshape(bal.num_groups, Gs)
    assert (slot_map < 0).any()  # the ragged case: some empty members
    W_loop = 0
    offsets_loop = np.zeros(slot_map.shape, np.int64)
    for g in range(slot_map.shape[0]):
        blks = slot_map[g][slot_map[g] >= 0]
        W_loop = max(W_loop, int(np.sum(np.asarray(widths)[blks])))
        off = 0
        for member in range(Gs):
            if slot_map[g, member] >= 0:
                offsets_loop[g, member] = off
                off += widths[slot_map[g, member]]
    W, offsets = lane_layout(widths, slot_map)
    assert W == W_loop
    placed = slot_map >= 0
    np.testing.assert_array_equal(offsets[placed], offsets_loop[placed])


# ---------------------------------------------------------------------------
# end-to-end CBMatrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family,kw", [
    ("uniform", dict(density=0.01)),
    ("power_law", {}),
    ("banded", {}),
    ("block_clustered", {}),
    ("pruned", {}),
])
@pytest.mark.parametrize("colagg", ["auto", True, False])
def test_cb_matrix_spmv_matches_oracle(family, kw, colagg):
    gen = matrices.FAMILIES[family]
    m, n = 160, 144
    r, c, v = gen(m, n, seed=11, **kw)
    cb = CBMatrix.from_coo(r, c, v, (m, n), block_size=16,
                           val_dtype=np.float32,
                           use_column_aggregation=colagg)
    x = np.random.default_rng(0).standard_normal(n).astype(np.float32)
    np.testing.assert_allclose(
        spmv_ref(cb, x), dense_oracle(r, c, v.astype(np.float32), (m, n), x),
        rtol=2e-4, atol=2e-4,
    )
    # to_dense agrees too
    dense = np.zeros((m, n), np.float32)
    np.add.at(dense, (r, c), v.astype(np.float32))
    np.testing.assert_allclose(cb.to_dense(), dense, rtol=1e-5, atol=1e-5)


def test_cb_matrix_spmm_matches_oracle():
    r, c, v = matrices.block_clustered(128, 128, seed=5)
    cb = CBMatrix.from_coo(r, c, v, (128, 128), block_size=16,
                           val_dtype=np.float32)
    X = np.random.default_rng(1).standard_normal((128, 8)).astype(np.float32)
    dense = np.zeros((128, 128), np.float32)
    np.add.at(dense, (r, c), v.astype(np.float32))
    np.testing.assert_allclose(spmm_ref(cb, X), dense @ X, rtol=2e-4, atol=2e-4)


def test_storage_accounting_matches_paper_model():
    """§4.4.1: CB storage ~ CSR parity, far below BSR."""
    r, c, v = matrices.uniform_random(512, 512, density=0.01, seed=2)
    cb = CBMatrix.from_coo(r, c, v, (512, 512), block_size=16,
                           val_dtype=np.float64,
                           use_column_aggregation=False)
    nnz = cb.nnz
    sizes = cb.nbytes_structure()
    csr = (512 + 1) * 4 + nnz * 4 + nnz * 8
    nblk = cb.num_blocks
    bsr = 256 * 8 * nblk + (512 // 16 + 1) * 4 + nblk * 4
    assert sizes["total"] < bsr / 4
    assert sizes["total"] < 4 * csr


# ---------------------------------------------------------------------------
# value layout + in-place value updates (dynamic sparsity)
# ---------------------------------------------------------------------------

@forall(coo_matrices(), sampled_from([8, 16]), examples=30)
def test_update_values_bit_identical_to_fresh_build(mat, B):
    rows, cols, vals, shape = mat
    cb = CBMatrix.from_coo(rows, cols, vals, shape, block_size=B,
                           val_dtype=np.float32)
    layout = cb.value_layout()
    r, c, _ = cb.to_coo()
    assert layout.count == len(r)
    rng = np.random.default_rng(layout.count)
    new_vals = rng.uniform(0.5, 2.0, layout.count).astype(np.float32)
    cb_up = cb.update_values(new_vals)
    cb_fresh = CBMatrix.from_coo(r, c, new_vals, shape, block_size=B,
                                 val_dtype=np.float32)
    np.testing.assert_array_equal(cb_up.packed, cb_fresh.packed)
    _, _, v_up = cb_up.to_coo()
    np.testing.assert_array_equal(v_up, new_vals)


def test_update_values_validates_length():
    cb = CBMatrix.from_coo(np.array([0, 5]), np.array([1, 3]),
                           np.array([1.0, 2.0], np.float32), (8, 8),
                           block_size=8, val_dtype=np.float32)
    with pytest.raises(ValueError, match="canonical"):
        cb.update_values(np.ones(3, np.float32))


def test_update_from_coo_dedups_and_rejects_drift():
    rows = np.array([0, 0, 2, 5])
    cols = np.array([1, 1, 2, 4])
    vals = np.array([1.0, 2.0, 3.0, 4.0], np.float32)
    cb = CBMatrix.from_coo(rows, cols, vals, (8, 8), block_size=8,
                           val_dtype=np.float32)
    # same coords (duplicates split differently) -> accepted, summed
    cb2 = cb.update_from_coo(rows, cols,
                             np.array([5.0, 5.0, 6.0, 7.0], np.float32))
    _, _, v = cb2.to_coo()
    np.testing.assert_array_equal(np.sort(v), [6.0, 7.0, 10.0])
    # a new coordinate is structure drift
    with pytest.raises(ValueError, match="structure drift"):
        cb.update_from_coo(np.array([0, 2, 5, 7]), cols, vals)


def test_value_layout_keys_are_canonical_order():
    rng = np.random.default_rng(3)
    rows = rng.integers(0, 40, 120)
    cols = rng.integers(0, 48, 120)
    vals = rng.standard_normal(120).astype(np.float32)
    for colagg in (True, False):
        cb = CBMatrix.from_coo(rows, cols, vals, (40, 48), block_size=16,
                               val_dtype=np.float32,
                               use_column_aggregation=colagg)
        layout = cb.value_layout()
        r, c, _ = cb.to_coo()
        np.testing.assert_array_equal(layout.keys, r * 48 + c)
        assert np.all(np.diff(layout.keys) > 0)
