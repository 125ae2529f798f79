"""Pallas TPU kernel: dense-tile CB-SpMV (paper Alg. 4, TPU-native, batched).

One grid step processes one *super-tile*: ``G`` FMT_DENSE sub-blocks
stacked vertically into a ``(G*B, B)`` value slab, each multiplied by its
own pre-gathered ``(B,)`` slice of x, producing a ``(G, B)`` stack of
partial result tiles. Partials are scatter-added into y by the jit'd
wrapper (ops.cb_spmv) — the deterministic TPU replacement for Alg. 4's
``atomicAdd`` (TPU has no atomics; XLA's scatter-add is deterministic and
the combine is order-independent, so the balanced group schedule is
preserved).

Batching G blocks per step amortizes per-step pipeline/DMA overhead — the
single-block version moved one (B, B) tile per step, far below what one
HBM->VMEM DMA can stream. The per-slot multiplies stay *separate* dots
(unrolled over the static G) because each slot contracts against its own
x slice; the slab still arrives as one contiguous DMA, which is where the
win is. Grid steps write disjoint output rows and never revisit them, so
``dimension_semantics=("parallel",)`` lets Mosaic split the grid across
megacore halves.

x is always pre-gathered through ``*_xidx`` (XLA gather), which folds the
column-aggregation ``restore_cols`` mapping or the trivial ``bcol*B + j``
mapping — Alg. 4's two x-access branches collapse into one path at
preprocessing time. (The old scalar-prefetch variant indexed x by block
column; a super-tile mixes block columns, so pre-gathering is the uniform
contract now.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat import pallas_call_tpu


def _kernel_batched(tiles_ref, xg_ref, out_ref, *, group_size: int,
                    block_size: int):
    """One super-tile: G unrolled (B, B) @ (B,) matvecs, one output stack."""
    B = block_size
    for g in range(group_size):
        tile = tiles_ref[0, g * B : (g + 1) * B, :]   # (B, B)
        xb = xg_ref[0, g]                             # (B,)
        out_ref[0, g, :] = jnp.dot(
            tile.astype(jnp.float32), xb.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_dense_spmv_batched(
    tiles: jax.Array,   # (gd, G*B, B) stacked super-tiles
    xg: jax.Array,      # (gd, G, B) pre-gathered x values per slot
    *,
    interpret: bool,
) -> jax.Array:
    """Per-slot partials for every super-tile — (gd, G, B) float32."""
    gd, G, B = xg.shape
    return pallas_call_tpu(
        functools.partial(_kernel_batched, group_size=G, block_size=B),
        grid=(gd,),
        in_specs=[
            pl.BlockSpec((1, G * B, B), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, G, B), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, B), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((gd, G, B), jnp.float32),
        dimension_semantics=("parallel",),
        interpret=interpret,
        name="cb_block_dense_spmv_batched",
    )(tiles, xg)
