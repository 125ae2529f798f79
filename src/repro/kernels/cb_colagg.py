"""Pallas TPU kernel: column-compacted micro-panel CB-SpMV (batched).

FMT_CSR blocks (intermediate sparsity) become dense (B, k) panels after
per-block column compaction — the TPU re-expression of the paper's
block-aware column aggregation (§3.3.1): all-zero columns are dropped at
preprocessing time so every VPU lane that loads data does useful work,
the TPU analogue of the ">= 50% warp utilization" guarantee.

One grid step = one *panel group*: many panels lane-packed side by side
into a fused ``(B, W)`` slab. Lane->slot routing is positional — slot =
``lane // SUBLANE`` — because the packer rounds every panel's width to a
SUBLANE multiple (its width bucket) and lays panels at aligned offsets.
A panel wider than one slot simply owns several consecutive slots whose
partials the scatter-add combine reunites (the combine is additive, so
splitting a panel's columns across slots is exact). The whole group then
reduces with

    tmp = slab * xg                 elementwise,   (B, W)
    out = tmp.T.reshape(S, SUBLANE, B).sum(axis=1)            (S, B)

— a plain strided lane reduction, O(B*W) work with *no* data-dependent
segment contraction, so the batched step costs the same FLOPs as the
panels it fuses on any backend. Batching buys the DMA/step amortization:
one contiguous slab per step instead of one panel per step, and a wide
outlier pads only its own group instead of the global ``Kp``. Grid steps
are independent (scatter-add combine outside), so
``dimension_semantics=("parallel",)`` enables megacore partitioning.

The CSR row_ptr of the portable format is *dissolved* at preprocessing:
rows are materialized into the panel's row axis, so the kernel needs no
row decoding at all — row structure is positional, which is exactly what
a systolic/vector unit wants (no indirection on the critical path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat import pallas_call_tpu
from repro.core.streams import SUBLANE
from repro import errors


def _panel_kernel_batched(panels_ref, xg_ref, out_ref, *, slots: int):
    slab = panels_ref[0].astype(jnp.float32)   # (B, W)
    xg = xg_ref[0].astype(jnp.float32)         # (1, W)
    tmp = slab * xg                            # (B, W)
    B = slab.shape[0]
    # Transpose first: Mosaic cannot split the lane axis in place
    # ((B, W) -> (B, S, SUBLANE)), but splits the sublane axis of (W, B).
    out_ref[0] = tmp.T.reshape(slots, SUBLANE, B).sum(axis=1)   # (S, B)


@functools.partial(jax.jit, static_argnames=("interpret",))
def panel_spmv_batched(
    panels: jax.Array,  # (gp, B, W) lane-packed panel groups, W % SUBLANE == 0
    xg: jax.Array,      # (gp, W) pre-gathered x values
    *,
    interpret: bool,
) -> jax.Array:
    """Per-slot partial y tiles — (gp, W // SUBLANE, B) float32.

    ``xg`` is viewed as ``(gp, 1, W)`` so its ``(1, 1, W)`` block's last
    two dimensions equal the array's, which Mosaic requires.
    """
    gp, B, W = panels.shape
    if W % SUBLANE:
        raise errors.InvalidArgError(f"packed width {W} not a multiple of {SUBLANE}")
    slots = W // SUBLANE
    return pallas_call_tpu(
        functools.partial(_panel_kernel_batched, slots=slots),
        grid=(gp,),
        in_specs=[
            pl.BlockSpec((1, B, W), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, W), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, slots, B), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((gp, slots, B), jnp.float32),
        dimension_semantics=("parallel",),
        interpret=interpret,
        name="cb_colagg_panel_spmv_batched",
    )(panels, xg.reshape(gp, 1, W))
