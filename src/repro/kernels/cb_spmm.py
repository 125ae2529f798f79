"""Pallas TPU kernel: batched CB-SpMM — block-sparse weights x dense acts.

The training/prefill path of ``CBSparseLinear`` and the solver subsystem's
multi-RHS ``matmat``: Y = A @ X with A a super-tile stream (``Gt`` B x B
weight tiles stacked into one ``(Gt*B, B)`` slab per grid step) and X
dense (n, N). SpMV (decode) is memory-bound, SpMM is compute-bound, so
the adaptation goal flips from locality to MXU occupancy; batching many
tiles per step amortizes per-step pipeline/DMA overhead exactly like the
SpMV super-block engine — the single-tile version moved one (B, B) tile
per step, far below what one HBM->VMEM DMA can stream.

Group contract (mirrors ``core/streams.SuperTileStream``):

  * grid is ``(num_n_tiles, num_groups)`` with the *group* dimension
    minor; one step consumes one super-tile slab and produces a
    ``(Gt, B, bn)`` stack of per-slot partial output tiles;
  * slot ``g`` contracts sublanes ``[g*B, (g+1)*B)`` of the slab against
    the X tile of block-column ``bcol[i, g]`` — an unrolled MXU dot per
    slot, because each slot owns its own activation tile. The slab still
    arrives as ONE contiguous DMA, which is where the win is;
  * X tiles are DMA'd per slot through the scalar-prefetched ``bcol``
    slot map (flattened to 1-D: SMEM pads a 2-D array's minor dimension
    to 128 words, which at ``Gt = 16`` made a ~2k-group map overflow the
    1 MiB of SMEM) — the virtual-pointer idea (data location resolved from
    prefetched metadata, payload fetched with a sequential DMA) mapped
    onto Pallas's pipeline. Empty slots carry ``bcol`` 0 and a zero
    tile, so they fetch X block 0 and contribute exact zeros;
  * every output cell is written exactly once (no revisiting, no
    accumulation order), so BOTH grid dimensions are ``"parallel"`` —
    Mosaic may split steps across megacore halves freely. The per-slot
    partials are scatter-added into y by the jit'd wrapper
    (``ops.cb_spmm``) with ONE fused combine over ``brow`` — the
    deterministic TPU replacement for atomicAdd, shared with the SpMV
    engine.

The activation tile width ``block_n`` must be a LANE (128) multiple —
``core/streams.spmm_block_n`` is the one place that rounding lives; this
kernel only asserts the invariant it established.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import pallas_call_tpu
from repro.core.streams import LANE
from repro import errors


def _spmm_group_kernel(bcol_ref, tiles_ref, *refs, group_size: int,
                       block_size: int):
    """One group: a (Gt, B, B) x (Gt, B, bn) batched MXU dot, one stack."""
    del bcol_ref  # consumed by the per-slot X index maps
    B, Gt = block_size, group_size
    out_ref = refs[-1]
    x_refs = refs[:-1]
    tiles = tiles_ref[0].reshape(Gt, B, B).astype(jnp.float32)
    xs = jnp.concatenate(
        [x_refs[g][0][None] for g in range(Gt)]
    ).astype(jnp.float32)                              # (Gt, B, bn)
    # HIGHEST: Mosaic's default f32 contraction is one bf16 pass (~1e-3
    # relative error on the chip); the engine promises f32 results.
    out_ref[0] = jax.lax.dot_general(
        tiles, xs,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def super_tile_spmm(
    tiles: jax.Array,   # (gt, Gt*B, B) stacked super-tiles
    bcol: jax.Array,    # (gt, Gt) int32 slot -> X block-row
    Xb: jax.Array,      # (nb, B, Npad) — X reshaped into B-row blocks
    *,
    block_n: int = LANE,
    interpret: bool,
) -> jax.Array:
    """Per-slot partial Y tiles — (gt, Gt, B, Npad) float32, ONE pallas_call.

    ``Npad`` (= ``Xb.shape[-1]``) must divide by ``block_n`` and
    ``block_n`` by LANE — both are arranged by ``ops.cb_spmm`` through
    ``spmm_block_n``; violations here are caller bugs, not data bugs.
    """
    gt, GtB, B = tiles.shape
    Gt = GtB // B
    _, _, Npad = Xb.shape
    if block_n % LANE:
        raise errors.InvalidArgError(f"block_n {block_n} not a multiple of {LANE} lanes")
    if Npad % block_n:
        raise errors.InvalidArgError(f"padded width {Npad} not a multiple of {block_n}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Npad // block_n, gt),
        in_specs=[
            pl.BlockSpec((1, Gt * B, B), lambda j, i, bcol: (i, 0, 0)),
            *[
                pl.BlockSpec(
                    (1, B, block_n),
                    lambda j, i, bcol, g=g: (bcol[i * Gt + g], 0, j),
                )
                for g in range(Gt)
            ],
        ],
        out_specs=pl.BlockSpec(
            (1, Gt, B, block_n), lambda j, i, bcol: (i, 0, 0, j)
        ),
    )
    return pallas_call_tpu(
        functools.partial(_spmm_group_kernel, group_size=Gt, block_size=B),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((gt, Gt, B, Npad), jnp.float32),
        dimension_semantics=("parallel", "parallel"),
        interpret=interpret,
        name="cb_super_tile_spmm",
    )(bcol.reshape(-1), tiles, *([Xb] * Gt))
