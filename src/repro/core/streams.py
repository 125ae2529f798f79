"""Kernel-facing typed streams derived from the portable CB format.

The portable ``CBMatrix`` stores mixed-dtype byte-packed blocks behind
virtual pointers (paper Fig. 7). Mosaic DMAs are typed, so the TPU kernels
consume *typed streams*: one stream per storage format, each a struct of
uniform arrays where block ``i`` owns row ``i`` of every array. Contiguity
— the actual locality mechanism of the paper — is preserved: a block's
payload occupies one contiguous row of the stream, fetched with a single
sequential HBM->VMEM DMA per grid step.

Three streams mirror the paper's three intra-block formats:

  * ``dense``  — (B, B) value tiles (FMT_DENSE blocks), MXU/VPU path.
  * ``panel``  — (B, K) column-compacted micro-panels (FMT_CSR blocks):
                 the block's non-zero columns are packed left, K padded to
                 a sublane multiple. This is the per-block analogue of the
                 paper's column aggregation — dense math on compacted data.
  * ``coo``    — element lists with the paper's packed coordinates
                 (``code = col << bits | row``), FMT_COO blocks.

Every stream carries per-block x gather indices (``*_xidx``) that already
encode the column-aggregation ``restore_cols`` mapping (or the trivial
``bcol*B + j`` mapping), so kernels never consult the restore maps at run
time — matching Alg. 3's precomputed ``cols_offset``/``restore_cols``
lookups but resolved at preprocessing time where they are free.

Three stream granularities share this layout:

  * ``SpMVStreams``       — one block per stream row (one per grid step).
  * ``SuperBlockStreams`` — ``build_super_streams``: up to ``group_size``
    blocks per stream row. Dense tiles stack vertically into a
    (G*B, B) super-tile; panel/COO payloads are width-*bucketed* (each
    block's width rounded to a sublane multiple) and lane-packed side by
    side, with a per-lane segment map telling the kernel which block slot
    each lane belongs to. The Alg. 2 balancer assigns blocks to groups so
    every grid step carries near-equal payload — the paper's inter-block
    load balancing applied at grid-step granularity.
  * ``SuperTileStream``   — ``build_super_tile_stream``: the SpMM
    (multi-RHS) analogue. Up to ``group_size`` block-dense weight tiles
    stack vertically into a (G*B, B) super-tile per grid step, with
    per-group ``brow``/``bcol`` slot maps; the same Alg. 2 balancer
    equalizes nnz per group. ``spmm_block_n`` is the single home of the
    SpMM lane rule (activation tile widths are LANE multiples).
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from . import balance as balance_mod
from . import column_agg as column_agg_mod
from .aggregation import coord_bits
from .cb_matrix import CBMatrix
from .formats import FMT_COO, FMT_CSR, FMT_DENSE
from repro import errors, obs

# ---------------------------------------------------------------------------
# Padding policy — the single place payload widths get aligned.
# ---------------------------------------------------------------------------

SUBLANE = 8  # float32 sublane count; payload widths align to this for DMA

LANE = 128  # VPU/MXU lane count; SpMM activation tile widths align to this


def pad_width(width: int, mult: int = SUBLANE) -> int:
    """Round a payload width up to the DMA-friendly multiple.

    Zero stays zero: an empty stream allocates genuinely empty arrays
    (the dispatch layer skips the format entirely), instead of the old
    behaviour of silently materializing a phantom ``(0, B, 8)`` buffer.
    """
    return -(-int(width) // mult) * mult


def spmm_block_n(n_cols: int, block_n: int = LANE) -> int:
    """The SpMM activation-tile width: lane-aligned, at most ``block_n``.

    THE single place the SpMM lane rule lives. The compiled Mosaic
    pipeline requires the minor (lane) dimension of every block to be a
    multiple of ``LANE`` (= 128 for float32); the old
    ``min(block_n, max(8, N))`` policy produced e.g. a 100-wide lane
    block for N=100, which only ever worked because tests run in
    interpret mode. Here ``N`` is rounded *up* to a lane multiple and
    capped at ``block_n`` (itself validated to be lane-aligned), so the
    chosen width always satisfies ``bn % LANE == 0`` and callers pad the
    activation matrix to ``ceil(N / bn) * bn`` columns.
    """
    if block_n % LANE:
        raise errors.InvalidArgError(
            f"block_n must be a multiple of {LANE} lanes, got {block_n}"
        )
    return min(block_n, pad_width(max(int(n_cols), 1), LANE))


# Aim each grid step's payload at about this many elements: big enough to
# amortize per-step DMA/launch overhead, small enough that many steps
# remain for the megacore "parallel" partitioning and the per-step one-hot
# scratch stays comfortably inside VMEM. These are the *default* knob
# values; the autotune subsystem (src/repro/autotune/) overrides them per
# matrix through ``group_size_for``.
TARGET_STEP_ELEMS = 4096

# Upper bound on blocks per grid step: caps the unrolled dense loop and
# the (W, G*B) segment one-hot width in the batched kernels.
MAX_GROUP_SIZE = 16


def group_size_for(
    block_size: int,
    target_step_elems: int = TARGET_STEP_ELEMS,
    max_group: int = MAX_GROUP_SIZE,
) -> int:
    """THE single home of the blocks-per-grid-step occupancy rule.

    ``target_step_elems // B^2`` blocks per step, clamped to
    ``[1, max_group]``. Every stream builder (``build_super_streams``,
    ``build_super_tile_stream``) routes its ``group_size=None`` default
    through here, and the autotuner's cost model sweeps the two knobs as
    per-matrix decisions instead of module constants.
    """
    g = int(target_step_elems) // (int(block_size) * int(block_size))
    return int(min(max(g, 1), int(max_group)))


def auto_group_size(block_size: int) -> int:
    """Occupancy heuristic at the default knobs (see ``group_size_for``)."""
    return group_size_for(block_size)


def even_group(count: int, group_size: int) -> tuple[int, int]:
    """(num_groups, slots per group) for ``count`` blocks at target G.

    Slots are evened across the ``ceil(count / G)`` groups so the last
    group is never mostly empty padding (count=40, G=16 -> 3 groups of
    14, not two full ones plus a third at 8/16). Shared by the host-side
    packer and the jit-side regroup so both agree on group geometry.
    """
    if count == 0:
        return 0, group_size
    ng = -(-count // group_size)
    return ng, -(-count // ng)


@dataclasses.dataclass
class SpMVStreams:
    """Typed per-format streams for the CB-SpMV kernels.

    Array fields are jax/numpy arrays (pytree leaves); the ints are static
    metadata. Block order within each stream is the balanced slot order of
    the source ``CBMatrix`` — the kernels' scatter-add combine makes the
    result independent of order, so the paper's load-balanced schedule is
    kept verbatim.
    """

    # -- static ---------------------------------------------------------
    block_size: int
    m: int
    n: int
    mb: int               # number of block rows = ceil(m / B)
    colagg_applied: bool
    # -- dense tile stream ----------------------------------------------
    dense_tiles: jax.Array   # (nd, B, B) val
    dense_brow: jax.Array    # (nd,) int32
    dense_xidx: jax.Array    # (nd, B) int32 global x index per tile column
    # -- panel stream (CSR blocks, column-compacted) ---------------------
    panel_vals: jax.Array    # (np_, B, Kp) val
    panel_brow: jax.Array    # (np_,) int32
    panel_xidx: jax.Array    # (np_, Kp) int32
    # -- coo element stream ----------------------------------------------
    coo_codes: jax.Array     # (nc, Ep) int32 packed (col << bits | row)
    coo_vals: jax.Array      # (nc, Ep) val (0 on padding)
    coo_brow: jax.Array      # (nc,) int32
    coo_xidx: jax.Array      # (nc, Ep) int32

    @property
    def num_dense(self) -> int:
        return self.dense_tiles.shape[0]

    @property
    def num_panel(self) -> int:
        return self.panel_vals.shape[0]

    @property
    def num_coo(self) -> int:
        return self.coo_codes.shape[0]

    def device_put(self) -> "SpMVStreams":
        return jax.tree_util.tree_map(jax.numpy.asarray, self)

    def padded_work(self) -> dict:
        """Elements each kernel actually streams, padding included."""
        B = self.block_size
        return {
            "dense": int(self.num_dense * B * B),
            "panel": int(self.num_panel * B * self.panel_vals.shape[-1]),
            "coo": int(self.num_coo * self.coo_codes.shape[-1]),
        }


jax.tree_util.register_dataclass(
    SpMVStreams,
    data_fields=[
        "dense_tiles", "dense_brow", "dense_xidx",
        "panel_vals", "panel_brow", "panel_xidx",
        "coo_codes", "coo_vals", "coo_brow", "coo_xidx",
    ],
    meta_fields=["block_size", "m", "n", "mb", "colagg_applied"],
)


def _block_x_indices(cb: CBMatrix, brow: int, bcol: int) -> np.ndarray:
    """Global x index for each of the B columns of block (brow, bcol)."""
    return column_agg_mod.restore_for_block(
        cb.colagg, brow, bcol, cb.block_size, cb.shape[1]
    ).astype(np.int32)


def _collect_blocks(cb: CBMatrix):
    """Walk the CBMatrix once, typing each block's payload for its stream.

    Returns ``(dense, panels, coos)`` where
      dense  — (brow, (B, B) tile, (B,) xidx, nnz) per FMT_DENSE block,
      panels — (brow, (B, k) compacted panel, (k,) xidx) per FMT_CSR,
      coos   — (brow, (e,) codes, (e,) vals, (e,) xidx) per FMT_COO.
    """
    B = cb.block_size
    bits = coord_bits(B)
    vdt = cb.val_dtype
    dense, panels, coos = [], [], []
    with obs.span("cb.streams.collect"):
        for brow, bcol, fmt, r, c, v in cb.iter_blocks():
            if fmt == FMT_DENSE:
                tile = np.zeros((B, B), dtype=vdt)
                tile[r, c] = v
                dense.append((brow, tile, _block_x_indices(cb, brow, bcol),
                              len(v)))
            elif fmt == FMT_CSR:
                ucols, rank = np.unique(c, return_inverse=True)
                panel = np.zeros((B, len(ucols)), dtype=vdt)
                panel[r, rank] = v
                xidx = cb.global_x_index(brow, bcol, ucols).astype(np.int32)
                panels.append((brow, panel, xidx))
            elif fmt == FMT_COO:
                codes = (c.astype(np.int64) << bits) | r.astype(np.int64)
                xidx = cb.global_x_index(brow, bcol, c).astype(np.int32)
                coos.append((brow, codes.astype(np.int32), v.astype(vdt),
                             xidx))
            else:  # pragma: no cover - format codes are exhaustive
                raise errors.InvalidArgError(f"unknown format {fmt}")
    return dense, panels, coos


def build_streams(cb: CBMatrix) -> SpMVStreams:
    """Derive the typed kernel streams from a CBMatrix (host-side).

    The packed-coordinate bit layout is fixed by ``aggregation.coord_bits``
    — the kernels and oracles recompute it from the block size, so it is
    deliberately not a parameter here (an encoder-side override would
    silently desync the decoders).
    """
    B = cb.block_size
    bits = coord_bits(B)
    m, n = cb.shape
    mb = -(-m // B)
    vdt = cb.val_dtype

    dense, panels, coos = _collect_blocks(cb)

    # ---- dense stream ---------------------------------------------------
    nd = len(dense)
    d_tiles = (np.stack([t for _, t, _, _ in dense]) if nd
               else np.zeros((0, B, B), vdt))
    d_brow = np.asarray([b for b, _, _, _ in dense], np.int32)
    d_xidx = (np.stack([x for _, _, x, _ in dense]).astype(np.int32) if nd
              else np.zeros((0, B), np.int32))

    # ---- panel stream ---------------------------------------------------
    np_ = len(panels)
    Kp = pad_width(max((p.shape[1] for _, p, _ in panels), default=0))
    p_vals = np.zeros((np_, B, Kp), vdt)
    p_brow = np.zeros(np_, np.int32)
    p_xidx = np.zeros((np_, Kp), np.int32)
    for i, (brow, panel, xidx) in enumerate(panels):
        k = panel.shape[1]
        p_vals[i, :, :k] = panel
        p_brow[i] = brow
        p_xidx[i, :k] = xidx

    # ---- coo stream -----------------------------------------------------
    nc = len(coos)
    Ep = pad_width(max((len(v) for _, _, v, _ in coos), default=0))
    c_codes = np.zeros((nc, Ep), np.int32)
    c_vals = np.zeros((nc, Ep), vdt)
    c_brow = np.zeros(nc, np.int32)
    c_xidx = np.zeros((nc, Ep), np.int32)
    for i, (brow, codes, vals, xidx) in enumerate(coos):
        e = len(vals)
        c_codes[i, :e] = codes
        c_vals[i, :e] = vals
        c_brow[i] = brow
        c_xidx[i, :e] = xidx

    return SpMVStreams(
        block_size=B, m=m, n=n, mb=mb, colagg_applied=cb.colagg.applied,
        dense_tiles=d_tiles, dense_brow=d_brow, dense_xidx=d_xidx,
        panel_vals=p_vals, panel_brow=p_brow, panel_xidx=p_xidx,
        coo_codes=c_codes, coo_vals=c_vals, coo_brow=c_brow, coo_xidx=c_xidx,
    )


# ---------------------------------------------------------------------------
# Super-block streams: the batched execution engine's input format.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SuperBlockStreams:
    """Typed streams with many blocks fused per stream row.

    One stream row = one Pallas grid step. Layouts per format:

      * dense — tiles stacked vertically: slot ``g`` of a group owns
        sublanes ``[g*B, (g+1)*B)`` of the ``(Gd*B, B)`` super-tile; its
        partial lands in row ``g`` of the ``(Gd, B)`` output tile.
      * panel / coo — payloads lane-packed side by side at
        sublane-aligned offsets (each block's width rounded up to
        ``SUBLANE`` — its width *bucket*), so a wide outlier pads only
        its own group. Lane->slot routing is **implicit**: slot =
        ``lane // SUBLANE``. A block wider than one slot occupies
        ``width / SUBLANE`` consecutive slots, each carrying the block's
        row in ``*_brow``; the pieces' partials are reunited by the
        additive scatter combine, which is exactly why no explicit
        segment map is needed — and why the kernels can split a fused
        payload with a plain reshape-sum instead of a data-dependent
        segment contraction (O(payload) on every backend).

    Slots that the packer left empty have zero payload and ``brow`` 0:
    they scatter-add zeros into block-row 0, which is exact.
    """

    # -- static ---------------------------------------------------------
    block_size: int
    m: int
    n: int
    mb: int
    colagg_applied: bool
    group_size: int          # requested blocks per step (packer target)
    # -- dense super-tiles ----------------------------------------------
    dense_tiles: jax.Array   # (gd, Gd*B, B) val
    dense_brow: jax.Array    # (gd, Gd) int32
    dense_xidx: jax.Array    # (gd, Gd, B) int32
    # -- lane-packed panel groups (Sp = Wp // SUBLANE slots) -------------
    panel_vals: jax.Array    # (gp, B, Wp) val
    panel_brow: jax.Array    # (gp, Sp) int32 slot -> block row
    panel_xidx: jax.Array    # (gp, Wp) int32
    # -- lane-packed coo groups (Sc = Wc // SUBLANE slots) ---------------
    coo_codes: jax.Array     # (gc, Wc) int32 packed (col << bits | row)
    coo_vals: jax.Array      # (gc, Wc) val (0 on padding)
    coo_brow: jax.Array      # (gc, Sc) int32
    coo_xidx: jax.Array      # (gc, Wc) int32

    @property
    def num_dense_groups(self) -> int:
        return self.dense_tiles.shape[0]

    @property
    def num_panel_groups(self) -> int:
        return self.panel_vals.shape[0]

    @property
    def num_coo_groups(self) -> int:
        return self.coo_codes.shape[0]

    def device_put(self) -> "SuperBlockStreams":
        return jax.tree_util.tree_map(jax.numpy.asarray, self)

    def padded_work(self) -> dict:
        """Elements each kernel streams per full pass, padding included."""
        return {
            "dense": int(np.prod(self.dense_tiles.shape)),
            "panel": int(np.prod(self.panel_vals.shape)),
            "coo": int(np.prod(self.coo_codes.shape)),
        }

    @property
    def val_itemsize(self) -> int:
        """Bytes per value element (payload dtype width)."""
        return int(np.dtype(self.dense_tiles.dtype).itemsize)

    def region_nbytes(self) -> dict:
        """Byte size of every device buffer one SpMV pass touches.

        Read-only shape metadata (no values are read), keyed by buffer
        name in DMA order plus the ``x``/``y`` operand vectors — the
        address-space layout the locality profiler
        (``repro.obs.locality``) models traffic over.
        """
        vb = self.val_itemsize
        ib = np.dtype(np.int32).itemsize
        return {
            "dense_tiles": int(self.dense_tiles.size) * vb,
            "dense_xidx": int(self.dense_xidx.size) * ib,
            "panel_vals": int(self.panel_vals.size) * vb,
            "panel_xidx": int(self.panel_xidx.size) * ib,
            "coo_codes": int(self.coo_codes.size) * ib,
            "coo_vals": int(self.coo_vals.size) * vb,
            "coo_xidx": int(self.coo_xidx.size) * ib,
            "x": int(self.n) * vb,
            "y": int(self.m) * vb,
        }


jax.tree_util.register_dataclass(
    SuperBlockStreams,
    data_fields=[
        "dense_tiles", "dense_brow", "dense_xidx",
        "panel_vals", "panel_brow", "panel_xidx",
        "coo_codes", "coo_vals", "coo_brow", "coo_xidx",
    ],
    meta_fields=["block_size", "m", "n", "mb", "colagg_applied", "group_size"],
)


def lane_layout(widths, slot_map: np.ndarray) -> tuple[int, np.ndarray]:
    """Shared payload width ``W`` and per-(group, member) lane offsets.

    ``slot_map[g, j]`` is the block in member ``j`` of group ``g`` (-1 =
    empty); members are laid side by side, so ``W = max_g sum(widths)``.
    Vectorised, so packing stays linear in the block count. Offsets of
    empty members are meaningless.
    """
    w = np.asarray(widths, np.int64)
    member_w = np.where(slot_map >= 0, w[np.maximum(slot_map, 0)], 0)
    W = int(member_w.sum(axis=1).max()) if member_w.size else 0
    return W, np.cumsum(member_w, axis=1) - member_w


def build_super_streams(
    cb: CBMatrix, group_size: int | None = None
) -> SuperBlockStreams:
    """Pack CB blocks into balanced super-block groups (host-side).

    ``group_size=None`` picks ``group_size_for(B)`` — the occupancy
    heuristic targeting ~``TARGET_STEP_ELEMS`` payload elements per grid
    step. Group assignment reuses the paper's Alg. 2 heap balancer
    (``balance.grid_group_balance``): dense groups balance nnz across
    uniform-shape super-tiles; panel/COO groups balance *bucketed width*
    so the shared array width ``W = max_g sum(widths)`` — the padded
    payload every step DMAs — is as small and as equal as the block mix
    allows.
    """
    B = cb.block_size
    G = group_size_for(B) if group_size is None else int(group_size)
    if G < 1:
        raise errors.InvalidArgError(f"group_size must be >= 1, got {G}")
    with obs.span("cb.build_super_streams", group_size=G):
        dense, panels, coos = _collect_blocks(cb)
        with obs.span("cb.streams.layout"):
            return _pack_super_streams(cb, G, dense, panels, coos)


def _pack_super_streams(cb: CBMatrix, G: int, dense, panels,
                        coos) -> SuperBlockStreams:
    """Lay ``_collect_blocks``' payloads out in balanced groups of ``G``
    (``build_super_streams``)."""
    B = cb.block_size
    m, n = cb.shape
    mb = -(-m // B)
    vdt = cb.val_dtype

    # ---- dense: nnz-balanced tiles, evened slots per super-tile ---------
    nd = len(dense)
    if nd:
        _, Gd = even_group(nd, G)
        with obs.span("cb.streams.balance"):
            bal = balance_mod.grid_group_balance(
                np.asarray([e[3] for e in dense], np.int64), Gd
            )
        gd = bal.num_groups
        d_tiles = np.zeros((gd, Gd * B, B), vdt)
        d_brow = np.zeros((gd, Gd), np.int32)
        d_xidx = np.zeros((gd, Gd, B), np.int32)
        for s, blk in enumerate(bal.slots):
            if blk < 0:
                continue
            g, slot = divmod(s, Gd)
            brow, tile, xidx, _ = dense[blk]
            d_tiles[g, slot * B : (slot + 1) * B, :] = tile
            d_brow[g, slot] = brow
            d_xidx[g, slot] = xidx
    else:
        d_tiles = np.zeros((0, G * B, B), vdt)
        d_brow = np.zeros((0, G), np.int32)
        d_xidx = np.zeros((0, G, B), np.int32)

    # ---- panel / coo: lane-packed, width-balanced -----------------------
    def _pack_lanes(widths, payload_rows):
        """Assign blocks to groups by bucketed width and lay out lanes.

        ``widths[i]`` is block i's bucketed lane count (a SUBLANE
        multiple). Returns the per-(group, member) block index map
        (-1 = empty), each member's lane offset, and zeroed packed
        arrays sized to the balanced width ``W = max_g sum(widths)``
        with a per-slot brow array of ``W // SUBLANE`` slots.
        """
        _, Gs = even_group(len(widths), G)
        with obs.span("cb.streams.balance"):
            bal = balance_mod.grid_group_balance(
                np.asarray(widths, np.int64), Gs)
        ng = bal.num_groups
        slot_map = bal.slots.reshape(ng, Gs)
        W, offsets = lane_layout(widths, slot_map)
        vals = np.zeros((ng, payload_rows, W) if payload_rows else (ng, W), vdt)
        brow = np.zeros((ng, W // SUBLANE), np.int32)
        xidx = np.zeros((ng, W), np.int32)
        return slot_map, offsets, vals, brow, xidx

    def _place_brow(brow_arr, g, off, w, brow):
        """A block's ``w`` lanes span ``w // SUBLANE`` consecutive slots,
        every one pointing at the block's row (pieces merge in the
        scatter-add)."""
        brow_arr[g, off // SUBLANE : (off + w) // SUBLANE] = brow

    np_ = len(panels)
    if np_:
        widths = [pad_width(p.shape[1]) for _, p, _ in panels]
        slot_map, offsets, p_vals, p_brow, p_xidx = _pack_lanes(
            widths, payload_rows=B
        )
        for (g, member), blk in np.ndenumerate(slot_map):
            if blk < 0:
                continue
            brow, panel, xidx = panels[blk]
            k = panel.shape[1]
            off = int(offsets[g, member])
            p_vals[g, :, off : off + k] = panel
            p_xidx[g, off : off + k] = xidx
            _place_brow(p_brow, g, off, widths[blk], brow)
    else:
        p_vals = np.zeros((0, B, 0), vdt)
        p_brow = np.zeros((0, 0), np.int32)
        p_xidx = np.zeros((0, 0), np.int32)

    nc = len(coos)
    if nc:
        widths = [pad_width(len(v)) for _, _, v, _ in coos]
        slot_map, offsets, c_vals, c_brow, c_xidx = _pack_lanes(
            widths, payload_rows=0
        )
        c_codes = np.zeros((c_vals.shape[0], c_vals.shape[-1]), np.int32)
        for (g, member), blk in np.ndenumerate(slot_map):
            if blk < 0:
                continue
            brow, codes, vals, xidx = coos[blk]
            e = len(vals)
            off = int(offsets[g, member])
            c_codes[g, off : off + e] = codes
            c_vals[g, off : off + e] = vals
            c_xidx[g, off : off + e] = xidx
            _place_brow(c_brow, g, off, widths[blk], brow)
    else:
        c_codes = np.zeros((0, 0), np.int32)
        c_vals = np.zeros((0, 0), vdt)
        c_brow = np.zeros((0, 0), np.int32)
        c_xidx = np.zeros((0, 0), np.int32)

    return SuperBlockStreams(
        block_size=B, m=m, n=n, mb=mb, colagg_applied=cb.colagg.applied,
        group_size=G,
        dense_tiles=d_tiles, dense_brow=d_brow, dense_xidx=d_xidx,
        panel_vals=p_vals, panel_brow=p_brow, panel_xidx=p_xidx,
        coo_codes=c_codes, coo_vals=c_vals, coo_brow=c_brow, coo_xidx=c_xidx,
    )


# ---------------------------------------------------------------------------
# Transposed streams: the solver subsystem's rmatvec path.
# ---------------------------------------------------------------------------

def transpose_cb(cb: CBMatrix) -> CBMatrix:
    """Rebuild the full CB pipeline for ``A^T`` (host-side, plan time).

    Krylov methods on nonsymmetric systems (BiCGStab's shadow residual,
    least-squares solves) need ``A^T @ y`` with the same amortized-
    preprocessing story as ``A @ x``. Rather than bolt a transposed
    execution mode onto the kernels (which would double every kernel's
    surface), the transpose gets its *own* CB structure: collect the
    matrix's triplets in original global coordinates, swap them, and run
    the whole preprocessing pipeline again. Block formats, column
    aggregation and balance are re-decided for A^T's structure — the
    transpose of a panel-heavy matrix may well be COO-heavy.

    Triplets are gathered in canonical row-major order of the transpose
    so the result is bit-identical to building ``CBMatrix.from_coo`` on
    the transposed triplets directly (determinism contract relied on by
    the solver tests).
    """
    B = cb.block_size
    m, n = cb.shape
    rs, cs, vs = [], [], []
    for brow, bcol, _fmt, r, c, v in cb.iter_blocks():
        gc = cb.global_x_index(brow, bcol, c)
        rs.append(brow * B + r.astype(np.int64))
        cs.append(gc.astype(np.int64))
        vs.append(v)
    if rs:
        r_all = np.concatenate(rs)
        c_all = np.concatenate(cs)
        v_all = np.concatenate(vs)
    else:
        r_all = c_all = np.zeros(0, np.int64)
        v_all = np.zeros(0, cb.val_dtype)
    order = np.lexsort((r_all, c_all))  # row-major in transposed coords
    return CBMatrix.from_coo(
        c_all[order], r_all[order], v_all[order], (n, m),
        block_size=B, val_dtype=cb.val_dtype, thresholds=cb.thresholds,
    )


def build_transposed_super_streams(
    cb: CBMatrix, group_size: int | None = None
) -> SuperBlockStreams:
    """Batched super-block streams for ``A^T`` (see :func:`transpose_cb`)."""
    return build_super_streams(transpose_cb(cb), group_size=group_size)


# ---------------------------------------------------------------------------
# SpMM tile stream: block-dense weights for the training/prefill path.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TileStream:
    """Block-dense (BSR-like) stream for CB-SpMM.

    Blocks are sorted in canonical ``(brow, bcol)`` order — BOTH builders
    (``build_tile_stream`` from raw COO, ``tile_stream_from_cb`` from the
    full CB pipeline) emit this exact order, so two streams of the same
    matrix are bit-identical regardless of which path produced them.
    Every block row owns at least one (possibly all-zero) coverage tile;
    the batched kernel's scatter-add combine no longer *needs* coverage
    for initialization (the accumulator starts at zero), but the
    guarantee is kept so stream geometry stays stable across builders.
    """

    block_size: int
    m: int
    n: int
    mb: int
    nb: int
    tiles: jax.Array   # (nt, B, B)
    brow: jax.Array    # (nt,) int32, ascending
    bcol: jax.Array    # (nt,) int32, ascending within each block row

    @property
    def num_tiles(self) -> int:
        return self.tiles.shape[0]


jax.tree_util.register_dataclass(
    TileStream,
    data_fields=["tiles", "brow", "bcol"],
    meta_fields=["block_size", "m", "n", "mb", "nb"],
)


def build_tile_stream(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    shape: tuple[int, int],
    block_size: int,
) -> TileStream:
    """Build the block-dense stream directly from COO triplets."""
    from .blocking import partition_coo

    m, n = shape
    B = block_size
    mb, nb = -(-m // B), -(-n // B)
    part = partition_coo(rows, cols, vals, shape, B)

    tiles, brows, bcols = [], [], []
    for i in range(part.num_blocks):
        r, c, v = part.block_elems(i)
        tile = np.zeros((B, B), dtype=v.dtype)
        tile[r, c] = v
        tiles.append(tile)
        brows.append(int(part.blk_row_idx[i]))
        bcols.append(int(part.blk_col_idx[i]))

    # Coverage: every block row must own >= 1 tile (stable stream geometry).
    present = set(brows)
    for rb in range(mb):
        if rb not in present:
            tiles.append(np.zeros((B, B), dtype=vals.dtype))
            brows.append(rb)
            bcols.append(0)

    # Canonical (brow, bcol) order — bit-identical to tile_stream_from_cb.
    order = np.lexsort((np.asarray(bcols), np.asarray(brows)))
    tiles_arr = np.stack(tiles)[order] if tiles else np.zeros((0, B, B), vals.dtype)
    return TileStream(
        block_size=B, m=m, n=n, mb=mb, nb=nb,
        tiles=tiles_arr,
        brow=np.asarray(brows, np.int32)[order],
        bcol=np.asarray(bcols, np.int32)[order],
    )


def tile_stream_from_cb(cb: CBMatrix) -> TileStream:
    """Densify every CB block into the tile stream (all formats -> tiles).

    Used when the SpMM path must run over a matrix preprocessed with the
    full CB pipeline; x-index indirection (column aggregation) is folded
    back to original coordinates so the stream is position-faithful.
    """
    B = cb.block_size
    m, n = cb.shape
    mb, nb = -(-m // B), -(-n // B)

    # One pass over blocks to collect flat triplets (block granularity),
    # then pure batch ops — no per-element Python.
    rs, gcs, vs, brs = [], [], [], []
    for brow, bcol, fmt, r, c, v in cb.iter_blocks():
        gc = cb.global_x_index(brow, bcol, c)
        rs.append(np.asarray(r, np.int64))
        gcs.append(np.asarray(gc, np.int64))
        vs.append(v)
        brs.append(np.full(len(v), brow, np.int64))
    if rs:
        r_all = np.concatenate(rs)
        gc_all = np.concatenate(gcs)
        v_all = np.concatenate(vs)
        br_all = np.concatenate(brs)
    else:
        r_all = gc_all = br_all = np.zeros(0, np.int64)
        v_all = np.zeros(0, cb.val_dtype)

    key = br_all * nb + gc_all // B  # ascending unique keys = (brow, bcol)
    ukeys, inv = np.unique(key, return_inverse=True)
    tiles = np.zeros((len(ukeys), B, B), dtype=cb.val_dtype)
    np.add.at(tiles, (inv, r_all, gc_all % B), v_all)
    brow_arr = (ukeys // nb).astype(np.int32)
    bcol_arr = (ukeys % nb).astype(np.int32)

    # Coverage: every block row must own >= 1 tile (revisit init correctness).
    missing = np.setdiff1d(np.arange(mb, dtype=np.int32), brow_arr)
    if len(missing):
        tiles = np.concatenate(
            [tiles, np.zeros((len(missing), B, B), cb.val_dtype)]
        )
        brow_arr = np.concatenate([brow_arr, missing])
        bcol_arr = np.concatenate([bcol_arr, np.zeros(len(missing), np.int32)])
    # Canonical (brow, bcol) order — bit-identical to build_tile_stream.
    order = np.lexsort((bcol_arr, brow_arr))
    return TileStream(
        block_size=B, m=m, n=n, mb=mb, nb=nb,
        tiles=tiles[order],
        brow=brow_arr[order],
        bcol=bcol_arr[order],
    )


# ---------------------------------------------------------------------------
# Super-tile stream: the batched SpMM execution engine's input format.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SuperTileStream:
    """Tile stream with ``Gt`` weight tiles fused per grid step.

    One stream row = one Pallas grid step (per activation n-tile). Slot
    ``g`` of group ``i`` owns sublanes ``[g*B, (g+1)*B)`` of the
    ``(Gt*B, B)`` super-tile; ``brow``/``bcol`` are the per-group slot
    maps routing that slot's partial to output block-row ``brow[i, g]``
    and its activation DMA to X block-row ``bcol[i, g]``. Slots the
    packer left empty hold a zero tile with ``brow``/``bcol`` 0: they
    DMA X block 0 and scatter-add exact zeros into output row 0.

    Unlike the SpMV super streams there is no lane packing — dense
    ``(B, B)`` tiles are already uniform — so the only balancing axis is
    nnz per tile, which Alg. 2 equalizes across groups to keep each
    step's useful-FLOP fraction even.
    """

    # -- static ---------------------------------------------------------
    block_size: int
    m: int
    n: int
    mb: int
    nb: int
    group_size: int          # requested tiles per step (packer target)
    # -- data ------------------------------------------------------------
    tiles: jax.Array   # (gt, Gt*B, B)
    brow: jax.Array    # (gt, Gt) int32
    bcol: jax.Array    # (gt, Gt) int32

    @property
    def num_groups(self) -> int:
        return self.tiles.shape[0]

    @property
    def slots(self) -> int:
        return self.brow.shape[1]

    def padded_work(self) -> dict:
        """Weight elements one full sweep streams, padding included."""
        return {"tiles": int(np.prod(self.tiles.shape))}

    @property
    def val_itemsize(self) -> int:
        """Bytes per weight element (payload dtype width)."""
        return int(np.dtype(self.tiles.dtype).itemsize)

    def region_nbytes(self) -> dict:
        """Byte size of the weight buffer one SpMM sweep streams.

        Read-only shape metadata for the locality profiler; the X/Y
        activation regions depend on the activation width and are laid
        out by ``repro.obs.locality.access_stream_super_tile``.
        """
        return {"tiles": int(self.tiles.size) * self.val_itemsize}


jax.tree_util.register_dataclass(
    SuperTileStream,
    data_fields=["tiles", "brow", "bcol"],
    meta_fields=["block_size", "m", "n", "mb", "nb", "group_size"],
)


def build_super_tile_stream(
    ts: TileStream, group_size: int | None = None
) -> SuperTileStream:
    """Pack SpMM tiles into nnz-balanced super-tile groups (host-side).

    Mirrors ``build_super_streams`` for the tile stream: ``group_size=
    None`` picks ``group_size_for(B)``; tiles are assigned to groups by
    the Alg. 2 heap balancer (``balance.grid_group_balance``) on per-tile
    nnz, with slots evened via ``even_group`` so the tail group is never
    mostly padding. Group order inside the balancer result is preserved
    verbatim — the scatter-add combine makes the output independent of
    slot order, so the balanced schedule rides through unchanged.
    """
    B = ts.block_size
    G = group_size_for(B) if group_size is None else int(group_size)
    if G < 1:
        raise errors.InvalidArgError(f"group_size must be >= 1, got {G}")

    tiles = np.asarray(ts.tiles)
    brow = np.asarray(ts.brow)
    bcol = np.asarray(ts.bcol)
    nt = tiles.shape[0]
    if nt:
        _, Gt = even_group(nt, G)
        bal = balance_mod.grid_group_balance(
            np.count_nonzero(tiles, axis=(1, 2)).astype(np.int64), Gt
        )
        gt = bal.num_groups
        s_tiles = np.zeros((gt, Gt * B, B), tiles.dtype)
        s_brow = np.zeros((gt, Gt), np.int32)
        s_bcol = np.zeros((gt, Gt), np.int32)
        for s, blk in enumerate(bal.slots):
            if blk < 0:
                continue
            g, slot = divmod(s, Gt)
            s_tiles[g, slot * B : (slot + 1) * B, :] = tiles[blk]
            s_brow[g, slot] = brow[blk]
            s_bcol[g, slot] = bcol[blk]
    else:
        s_tiles = np.zeros((0, G * B, B), tiles.dtype)
        s_brow = np.zeros((0, G), np.int32)
        s_bcol = np.zeros((0, G), np.int32)

    return SuperTileStream(
        block_size=B, m=ts.m, n=ts.n, mb=ts.mb, nb=ts.nb, group_size=G,
        tiles=s_tiles, brow=s_brow, bcol=s_bcol,
    )


def super_tile_stream_from_cb(
    cb: CBMatrix, group_size: int | None = None
) -> SuperTileStream:
    """Full CB pipeline -> densified tiles -> balanced super-tile groups."""
    return build_super_tile_stream(tile_stream_from_cb(cb),
                                   group_size=group_size)


# ---------------------------------------------------------------------------
# Stream updaters: the dynamic-sparsity fast path at stream granularity.
#
# Every stream builder above permutes values (balanced slot order, lane
# packing, tile stacking) but decides the permutation from the sparsity
# pattern alone. The updaters record that permutation ONCE — by building
# the stream from a "shadow" CBMatrix whose payload values are canonical
# indices — and afterwards re-materialize a stream for fresh values with
# a single vectorized scatter, never re-running the builders.
# ---------------------------------------------------------------------------


def _index_cb(cb: CBMatrix) -> CBMatrix:
    """A shadow of ``cb`` whose payload values are ``canonical_rank + 1``.

    Same blocking / colagg / format / balance metadata; int64 values, all
    nonzero — so every value-sensitive step inside the stream builders
    (dense-tile nonzero recovery, nnz balancing, ``count_nonzero`` on
    densified tiles) sees the structure an all-nonzero real build would.
    Building any stream from the shadow therefore yields payload arrays
    holding ``src_index + 1`` at exactly the positions the real builder
    would place canonical value ``src_index`` — the value-scatter index,
    extracted with zero changes to the builders themselves.
    """
    from . import aggregation

    layout = cb.value_layout()
    B = cb.block_size
    n = cb.shape[1]
    elems, fmts, slot_idx = [], [], []
    for i in range(cb.num_slots):
        nnz = int(cb.nnz_per_blk[i])
        if nnz == 0:
            continue
        fmt = int(cb.type_per_blk[i])
        r, c, _v = aggregation.unpack_block(
            cb.packed, int(cb.vp_per_blk[i]), fmt, nnz, B, cb.val_dtype
        )
        brow = int(cb.blk_row_idx[i])
        bcol = int(cb.blk_col_idx[i])
        key = ((brow * B + r.astype(np.int64)) * n
               + cb.global_x_index(brow, bcol, c))
        rank = np.searchsorted(layout.keys, key)
        elems.append((r, c, rank + 1))
        fmts.append(fmt)
        slot_idx.append(i)
    packed = aggregation.aggregate_blocks(
        np.asarray(fmts, np.uint8), elems, B, np.dtype(np.int64)
    )
    vp = np.zeros_like(cb.vp_per_blk)
    nnzb = np.zeros_like(cb.nnz_per_blk)
    for j, i in enumerate(slot_idx):
        vp[i] = packed.vp_per_blk[j]
        nnzb[i] = len(elems[j][0])
    return dataclasses.replace(
        cb, val_dtype=np.dtype(np.int64), nnz_per_blk=nnzb,
        vp_per_blk=vp, packed=packed.packed,
    )


def _scatter_from_index(arr: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(flat positions, canonical source index) of a shadow payload array."""
    flat = np.asarray(arr).reshape(-1)
    pos = np.flatnonzero(flat)
    return pos, (flat[pos] - 1).astype(np.int64)


def _scatter_payload(shape, dtype, pos, src, vals):
    """Zeros of ``shape`` with ``vals[src]`` scattered at flat ``pos``.

    numpy in, numpy out (the cheap host path the benchmarks compare
    against a full rebuild); anything else goes through ``jax.numpy`` so
    the scatter is traceable inside jit (pos/src are static constants).
    """
    size = int(np.prod(shape))
    if isinstance(vals, np.ndarray):
        out = np.zeros(size, dtype)
        out[pos] = np.ascontiguousarray(vals, dtype)[src]
        return out.reshape(shape)
    import jax.numpy as jnp

    out = jnp.zeros((size,), dtype)
    if len(pos):
        out = out.at[pos].set(jnp.asarray(vals).astype(dtype)[src])
    return out.reshape(shape)


@dataclasses.dataclass(eq=False)
class SuperStreamUpdater:
    """Value-scatter index for a ``SuperBlockStreams`` layout.

    ``apply(canonical_vals)`` returns a stream bit-identical to
    ``build_super_streams`` on the same structure with those values
    (values in the canonical ``to_coo`` order), at vectorized-scatter
    cost. ``eq=False`` keeps the object identity-hashable so it can ride
    jit static metadata (same discipline as ``sparse.linear``'s spec).
    """

    template: SuperBlockStreams   # real metadata, zeroed payloads
    val_dtype: np.dtype
    dense_pos: np.ndarray
    dense_src: np.ndarray
    panel_pos: np.ndarray
    panel_src: np.ndarray
    coo_pos: np.ndarray
    coo_src: np.ndarray

    def apply(self, canonical_vals) -> SuperBlockStreams:
        t = self.template
        return dataclasses.replace(
            t,
            dense_tiles=_scatter_payload(
                t.dense_tiles.shape, self.val_dtype,
                self.dense_pos, self.dense_src, canonical_vals),
            panel_vals=_scatter_payload(
                t.panel_vals.shape, self.val_dtype,
                self.panel_pos, self.panel_src, canonical_vals),
            coo_vals=_scatter_payload(
                t.coo_vals.shape, self.val_dtype,
                self.coo_pos, self.coo_src, canonical_vals),
        )


def _super_updater_from_shadow(
    shadow: SuperBlockStreams, vdt: np.dtype
) -> SuperStreamUpdater:
    dense_pos, dense_src = _scatter_from_index(shadow.dense_tiles)
    panel_pos, panel_src = _scatter_from_index(shadow.panel_vals)
    coo_pos, coo_src = _scatter_from_index(shadow.coo_vals)
    template = dataclasses.replace(
        shadow,
        dense_tiles=np.zeros(shadow.dense_tiles.shape, vdt),
        panel_vals=np.zeros(shadow.panel_vals.shape, vdt),
        coo_vals=np.zeros(shadow.coo_vals.shape, vdt),
    )
    return SuperStreamUpdater(
        template=template, val_dtype=vdt,
        dense_pos=dense_pos, dense_src=dense_src,
        panel_pos=panel_pos, panel_src=panel_src,
        coo_pos=coo_pos, coo_src=coo_src,
    )


def super_stream_updater(
    cb: CBMatrix, group_size: int | None = None
) -> SuperStreamUpdater:
    """Record ``build_super_streams``'s value permutation once.

    The returned updater's ``apply`` matches a fresh
    ``build_super_streams(cb.update_values(v), group_size)`` bit for bit
    whenever the new values are nonzero (an exact 0.0 would change which
    elements a dense tile recovers — structure drift, not an update).
    """
    shadow = build_super_streams(_index_cb(cb), group_size=group_size)
    return _super_updater_from_shadow(shadow, np.dtype(cb.val_dtype))


def transposed_super_stream_updater(
    cb: CBMatrix, group_size: int | None = None
) -> SuperStreamUpdater:
    """Value-scatter index for the ``A^T`` stream, in **forward** order.

    ``transpose_cb`` re-runs the whole CB pipeline on swapped triplets
    but carries values through untouched, so transposing the shadow
    matrix lands forward canonical indices at the transposed stream's
    payload positions: one ``apply(forward_canonical_vals)`` updates the
    rmatvec path with no transposed-order bookkeeping anywhere.
    """
    shadow = build_super_streams(transpose_cb(_index_cb(cb)),
                                 group_size=group_size)
    return _super_updater_from_shadow(shadow, np.dtype(cb.val_dtype))


@dataclasses.dataclass(eq=False)
class SuperTileUpdater:
    """Value-scatter index for a ``SuperTileStream`` layout (SpMM path)."""

    template: SuperTileStream     # real slot maps, zeroed tiles
    val_dtype: np.dtype
    pos: np.ndarray
    src: np.ndarray

    def apply(self, canonical_vals) -> SuperTileStream:
        t = self.template
        return dataclasses.replace(
            t,
            tiles=_scatter_payload(t.tiles.shape, self.val_dtype,
                                   self.pos, self.src, canonical_vals),
        )


def super_tile_updater(
    cb: CBMatrix, group_size: int | None = None
) -> SuperTileUpdater:
    """Record ``super_tile_stream_from_cb``'s value permutation once."""
    shadow = super_tile_stream_from_cb(_index_cb(cb), group_size=group_size)
    vdt = np.dtype(cb.val_dtype)
    pos, src = _scatter_from_index(shadow.tiles)
    template = dataclasses.replace(
        shadow, tiles=np.zeros(shadow.tiles.shape, vdt)
    )
    return SuperTileUpdater(template=template, val_dtype=vdt,
                            pos=pos, src=src)
