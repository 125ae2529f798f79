"""Pallas TPU kernel: faithful block-COO CB-SpMV (paper Alg. 3, batched).

FMT_COO blocks (super-sparse) ship as element lists with the paper's
*packed coordinates*: ``code = col << bits | row`` (Alg. 3 decodes
``row = b & 15; col = b >> 4``; we generalize the mask to the block
size). One grid step consumes one *element group*: many blocks' element
lists lane-packed into a single ``(W,)`` payload at SUBLANE-aligned
offsets, so lane->slot routing is positional (slot = ``lane // SUBLANE``;
a block with many elements owns several consecutive slots, whose partial
tiles the additive scatter combine reunites). The kernel decodes
coordinates on-chip and scatters within each slot with a one-hot product
plus a strided lane reduction:

    row      = code & mask                   block-local row (Alg. 3)
    weighted = (val * xv)[:, None] * onehot(row)        (W, B)
    out      = weighted.reshape(S, SUBLANE, B).sum(lanes)   (S, B)

The one-hot is only ``B`` wide — identical per-element work to the
unbatched kernel — and the slot split is a free reshape, so batching
costs no extra FLOPs on any backend; it buys the step/DMA amortization
and per-group (instead of global ``Ep``) padding. The reduction order is
fixed by the contraction, so the result is exact and deterministic,
unlike ``atomicAdd``. Padding lanes carry ``val == 0`` and contribute
nothing regardless of their decoded coordinates.

x arrives pre-gathered (``coo_xidx`` folds the colagg ``restore_cols``
mapping or the trivial one — Alg. 3's two x branches resolved at
preprocessing). Steps write disjoint output rows, so
``dimension_semantics=("parallel",)`` allows megacore partitioning.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat import pallas_call_tpu
from repro.core.aggregation import coord_bits
from repro.core.streams import SUBLANE
from repro import errors


def _decode(codes, B):
    """Alg. 3 lines 11-12, generalized: row = code & mask, col = code >> bits.

    The mask is ``(1 << bits) - 1``, NOT ``B - 1``: for non-power-of-two
    block sizes (e.g. B=24, bits=5) ``B - 1`` has holes and corrupts rows.
    """
    bits = coord_bits(B)
    rows = codes & ((1 << bits) - 1)
    cols = codes >> bits
    return rows, cols


def _coo_kernel_batched(codes_ref, vals_ref, xg_ref, out_ref, *,
                        block_size: int, slots: int):
    B = block_size
    codes = codes_ref[0, 0]                     # (W,) int32
    vals = vals_ref[0, 0].astype(jnp.float32)   # (W,)
    xv = xg_ref[0, 0].astype(jnp.float32)       # (W,) pre-gathered
    rows, _ = _decode(codes, B)
    iota = jax.lax.broadcasted_iota(jnp.int32, (codes.shape[0], B), 1)
    onehot = (rows[:, None] == iota).astype(jnp.float32)     # (W, B)
    weighted = (vals * xv)[:, None] * onehot                 # (W, B)
    out_ref[0] = weighted.reshape(slots, SUBLANE, B).sum(axis=1)


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def coo_spmv_batched(
    codes: jax.Array,  # (gc, W) int32 lane-packed coordinates
    vals: jax.Array,   # (gc, W) values (0 on padding lanes)
    xg: jax.Array,     # (gc, W) pre-gathered x values
    *,
    block_size: int,
    interpret: bool,
) -> jax.Array:
    """Per-slot partial y tiles — (gc, W // SUBLANE, B) float32.

    Each operand is viewed as ``(gc, 1, W)`` so a ``(1, 1, W)`` block's
    last two dimensions equal the array's: Mosaic refuses a ``(1, W)``
    block over ``(gc, W)`` (the sublane dimension would be neither a
    multiple of 8 nor the whole axis).
    """
    gc, W = codes.shape
    if W % SUBLANE:
        raise errors.InvalidArgError(f"packed width {W} not a multiple of {SUBLANE}")
    slots = W // SUBLANE
    B = block_size
    return pallas_call_tpu(
        functools.partial(_coo_kernel_batched, block_size=B, slots=slots),
        grid=(gc,),
        in_specs=[pl.BlockSpec((1, 1, W), lambda i: (i, 0, 0))] * 3,
        out_specs=pl.BlockSpec((1, slots, B), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((gc, slots, B), jnp.float32),
        dimension_semantics=("parallel",),
        interpret=interpret,
        name="cb_coo_spmv_batched",
    )(*(a.reshape(gc, 1, W) for a in (codes, vals, xg)))
