"""Device-level CB-SpMV: the paper's load balancer, scaled to a mesh axis.

The paper balances sub-blocks across thread blocks (8 warp slots each);
here the same min-heap algorithm balances sub-blocks across the devices of
the ``model`` mesh axis (core/balance.device_load_balance). Equal block
count per device gives uniform shard shapes (a shard_map requirement) and
near-equal nnz gives near-equal work — the straggler story at mesh scale.

Pipeline:
  1. ``shard_streams``   (host) — pq-assign blocks to devices, pack each
     device's blocks into SuperBlockStreams with ``build_super_streams``
     (the one-chip packer, default group size, so every device has the
     same ``G``), pad every device to the largest group count, slot count
     and lane width with zero groups, slots and lanes, stack into
     leading-axis-``D`` arrays.
  2. ``distributed_spmv`` — shard_map over the model axis: each device
     runs the single-device batched kernels on its shard against a
     replicated x, then a single ``psum`` (or ``psum_scatter``) combines
     partial y.

x stays replicated (SpMV x is tiny relative to the matrix); y combine is
one collective — the communication-minimal schedule for 1D row-partitioned
SpMV (cf. the paper's related work on distributed SpMV [37]).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import errors, obs

from . import balance
from .cb_matrix import CBMatrix
from .streams import SuperBlockStreams, build_super_streams


def _pad_to(arr: np.ndarray, shape: tuple) -> np.ndarray:
    """``arr`` in the leading corner of a zero array of ``shape``."""
    out = np.zeros(shape, arr.dtype)
    if arr.size:
        out[tuple(map(slice, arr.shape))] = arr
    return out


@dataclasses.dataclass
class ShardedStreams:
    """Per-device packed streams stacked on a leading device axis.

    ``streams`` is one ``SuperBlockStreams`` whose every array has a
    leading dim ``D``: slice ``d`` is device ``d``'s packed groups, padded
    to the shape of the largest device.
    """

    num_devices: int
    streams: SuperBlockStreams
    device_nnz: np.ndarray    # (D,) achieved nnz per device (diagnostics)

    @property
    def load_imbalance(self) -> float:
        mean = self.device_nnz.mean()
        return float(self.device_nnz.max() / mean) if mean > 0 else 1.0

    def device_put(self, mesh: jax.sharding.Mesh,
                   axis: str = "model") -> "ShardedStreams":
        """Place each device's shard on its own device of ``mesh``.

        Every leaf is split along its leading (device) axis over ``axis``,
        so no device ever holds the whole stack.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(mesh, P(axis))
        return dataclasses.replace(
            self, streams=jax.device_put(self.streams, sharding))


def shard_streams(cb: CBMatrix, num_devices: int) -> ShardedStreams:
    """pq-balance CB blocks across devices and build uniform stacked
    packed streams."""
    with obs.span("cb.shard_streams", num_devices=num_devices):
        real_idx = np.flatnonzero(cb.nnz_per_blk > 0)
        with obs.span("cb.shard.balance"):
            result = balance.device_load_balance(cb.nnz_per_blk[real_idx],
                                                 num_devices)
        per_dev: list[SuperBlockStreams] = []
        gs = result.group_size
        with obs.span("cb.shard.build"):
            for d in range(num_devices):
                slots = result.slots[d * gs : (d + 1) * gs]
                blocks = real_idx[slots[slots >= 0]]
                per_dev.append(build_super_streams(_sub_matrix(cb, blocks)))
        with obs.span("cb.shard.stack"):
            stacked = _stack_uniform(per_dev)
        return ShardedStreams(
            num_devices=num_devices,
            streams=stacked,
            device_nnz=result.group_loads.copy(),
        )


def _stack_uniform(per_dev: list[SuperBlockStreams]) -> SuperBlockStreams:
    """Stack per-device packed streams on a leading axis, each array padded
    to the largest group count, slot count and lane width of any device.

    Padding groups, slots and lanes hold zero values, x index 0 and brow
    0, as the packer's own padding does, so they scatter-add exact zeros.
    A device with no groups of a format does not set that format's inner
    shape (an empty dense stream still carries ``G`` slots).
    """
    def stack(*arrs):
        full = [a for a in arrs if a.shape[0]] or arrs[:1]
        shape = (max(a.shape[0] for a in arrs),
                 *np.max([a.shape[1:] for a in full], axis=0))
        return np.stack([_pad_to(a, shape) for a in arrs])

    # tree_map over the dataclass keeps the (shared) meta of the first.
    return jax.tree_util.tree_map(stack, *per_dev)


def _sub_matrix(cb: CBMatrix, block_slots: np.ndarray) -> CBMatrix:
    """A view-style CBMatrix restricted to the given metadata slots."""
    return dataclasses.replace(
        cb,
        blk_row_idx=cb.blk_row_idx[block_slots],
        blk_col_idx=cb.blk_col_idx[block_slots],
        nnz_per_blk=cb.nnz_per_blk[block_slots],
        type_per_blk=cb.type_per_blk[block_slots],
        vp_per_blk=cb.vp_per_blk[block_slots],
        nnz=int(cb.nnz_per_blk[block_slots].sum()),
    )


def distributed_spmv(
    sharded: ShardedStreams,
    x: jax.Array,
    mesh: jax.sharding.Mesh,
    axis: str = "model",
    *,
    impl: str = "pallas",
    interpret: bool | None = None,
    combine: str = "psum_scatter",
) -> jax.Array:
    """y = A @ x with A's blocks pq-balanced over ``axis``; x replicated.

    ``combine`` picks the partial-y reduction:

      * ``"psum_scatter"`` (default) — each device keeps only its y shard
        after the reduce-scatter, so the combine moves ``m`` elements per
        device instead of ``D * m`` and the output stays sharded over
        ``axis`` (the ROADMAP scale-out item). The returned global array
        is sliced back to length ``m``.
      * ``"psum"`` — the legacy fully-replicated combine, kept for the
        multi-pod dry-run whose CPU stand-in lowering only exercises the
        all-reduce collective.
    """
    from jax.sharding import PartitionSpec as P

    from repro import compat
    from repro.kernels import ops

    if combine not in ("psum", "psum_scatter"):
        raise errors.InvalidArgError(f"unknown combine {combine!r}")
    dev_spec = jax.tree_util.tree_map(lambda _: P(axis), sharded.streams)
    m = sharded.streams.m
    D = sharded.num_devices
    m_pad = -(-m // D) * D  # reduce-scatter needs an axis divisible by D

    @partial(
        compat.shard_map,
        mesh=mesh,
        in_specs=(dev_spec, P()),
        out_specs=P() if combine == "psum" else P(axis),
        # pallas_call out_shapes carry no varying-mesh-axes info
        check_vma=False,
    )
    def run(streams_shard, x_rep):
        local = jax.tree_util.tree_map(lambda a: a[0], streams_shard)
        y = ops.cb_spmv(local, x_rep, impl=impl, interpret=interpret)
        if combine == "psum":
            return jax.lax.psum(y, axis)
        y_pad = jnp.pad(y, (0, m_pad - y.shape[0]))
        return jax.lax.psum_scatter(y_pad, axis, scatter_dimension=0,
                                    tiled=True)

    y = run(sharded.streams, x)
    if combine == "psum" or m == m_pad:
        return y  # still sharded over ``axis`` in the scatter case
    return y[:m]  # ragged tail: the slice re-gathers the last shard
