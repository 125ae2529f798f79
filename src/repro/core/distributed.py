"""Device-level CB-SpMV: the paper's load balancer, scaled to a mesh axis.

The paper balances sub-blocks across thread blocks (8 warp slots each);
here the same min-heap algorithm balances sub-blocks across the devices of
the ``model`` mesh axis (core/balance.device_load_balance). Equal block
count per device gives uniform shard shapes (a shard_map requirement) and
near-equal nnz gives near-equal work — the straggler story at mesh scale.

Pipeline:
  1. ``shard_streams``   (host) — pq-assign blocks to devices, build one
     SpMVStreams per device, pad every stream to the max per-device shape
     with zero blocks, stack into leading-axis-``D`` arrays.
  2. ``distributed_spmv`` — shard_map over the model axis: each device
     runs the single-device kernels on its shard against a replicated x,
     then a single ``psum`` (or ``psum_scatter``) combines partial y.

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
from .streams import SpMVStreams, build_streams


def _pad_axis0(arr: np.ndarray, target: int) -> np.ndarray:
    if arr.shape[0] == target:
        return arr
    pad = np.zeros((target - arr.shape[0],) + arr.shape[1:], arr.dtype)
    return np.concatenate([arr, pad], axis=0)


def _pad_axis_last(arr: np.ndarray, target: int) -> np.ndarray:
    if arr.shape[-1] == target:
        return arr
    widths = [(0, 0)] * (arr.ndim - 1) + [(0, target - arr.shape[-1])]
    return np.pad(arr, widths)


@dataclasses.dataclass
class ShardedStreams:
    """Per-device SpMV streams stacked on a leading device axis."""

    num_devices: int
    streams: SpMVStreams      # every array has leading dim D
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
    """pq-balance CB blocks across devices and build uniform stacked streams."""
    with obs.span("cb.shard_streams", num_devices=num_devices):
        real_idx = np.flatnonzero(cb.nnz_per_blk > 0)
        with obs.span("cb.shard.balance"):
            result = balance.device_load_balance(cb.nnz_per_blk[real_idx],
                                                 num_devices)
        per_dev: list[SpMVStreams] = []
        gs = result.group_size
        with obs.span("cb.shard.build"):
            for d in range(num_devices):
                slots = result.slots[d * gs : (d + 1) * gs]
                blocks = real_idx[slots[slots >= 0]]
                per_dev.append(build_streams(_sub_matrix(cb, blocks)))
        with obs.span("cb.shard.stack"):
            stacked = _stack_uniform(per_dev)
        return ShardedStreams(
            num_devices=num_devices,
            streams=stacked,
            device_nnz=result.group_loads.copy(),
        )


def _stack_uniform(per_dev: list[SpMVStreams]) -> SpMVStreams:
    """Stack per-device streams on a leading axis, each padded to the
    largest block count and inner width of any device."""
    # Uniform shapes: pad block counts and inner pads to the per-axis max.
    nd = max(s.num_dense for s in per_dev)
    np_ = max(s.num_panel for s in per_dev)
    nc = max(s.num_coo for s in per_dev)
    Kp = max(s.panel_vals.shape[2] for s in per_dev)
    Ep = max(s.coo_codes.shape[1] for s in per_dev)

    def pad(s: SpMVStreams) -> SpMVStreams:
        return SpMVStreams(
            block_size=s.block_size, m=s.m, n=s.n, mb=s.mb,
            colagg_applied=s.colagg_applied,
            dense_tiles=_pad_axis0(np.asarray(s.dense_tiles), nd),
            dense_brow=_pad_axis0(np.asarray(s.dense_brow), nd),
            dense_xidx=_pad_axis0(np.asarray(s.dense_xidx), nd),
            panel_vals=_pad_axis0(_pad_axis_last(np.asarray(s.panel_vals), Kp), np_),
            panel_brow=_pad_axis0(np.asarray(s.panel_brow), np_),
            panel_xidx=_pad_axis0(_pad_axis_last(np.asarray(s.panel_xidx), Kp), np_),
            coo_codes=_pad_axis0(_pad_axis_last(np.asarray(s.coo_codes), Ep), nc),
            coo_vals=_pad_axis0(_pad_axis_last(np.asarray(s.coo_vals), Ep), nc),
            coo_brow=_pad_axis0(np.asarray(s.coo_brow), nc),
            coo_xidx=_pad_axis0(_pad_axis_last(np.asarray(s.coo_xidx), Ep), nc),
        )

    # tree_map over dataclass keeps meta from the first element.
    return jax.tree_util.tree_map(
        lambda *xs: np.stack(xs), *[pad(s) for s in per_dev]
    )


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
