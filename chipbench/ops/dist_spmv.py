"""y = A @ x over a mesh: ``core.distributed.distributed_spmv``.

Set-up is ``shard_streams`` (blocks pq-balanced over the devices) and
``ShardedStreams.device_put``; x is replicated, y comes back sharded and
is gathered for the comparison only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import common
from chipbench.ops import spmv


def setup(data, traffic, devices, clock):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import compat
    from repro.core import CBMatrix
    from repro.core import distributed as dist
    from repro.kernels import ops

    D = len(devices)
    combine = traffic["combine"]
    with clock("from_coo"):
        cb = CBMatrix.from_coo(data.rows, data.cols, data.vals, data.shape,
                               block_size=traffic["block_size"])
    with clock("pack"):
        sharded = dist.shard_streams(cb, D)
        mesh = compat.make_mesh((D,), ("model",), devices=devices)
        placed = jax.block_until_ready(sharded.device_put(mesh))
    device_nnz = sharded.device_nnz
    stats = ops.spmv_launch_stats(
        jax.tree_util.tree_map(lambda a: a[0], sharded.streams))
    replicated = NamedSharding(mesh, P())

    def step(streams, x):
        return dist.distributed_spmv(
            dist.ShardedStreams(D, streams, device_nnz), x, mesh,
            combine=combine)

    return common.Setup(
        step=jax.jit(step),
        args=(placed.streams,),
        put=lambda x: jax.device_put(jnp.asarray(x), replicated),
        output=lambda y: y,
        grid_steps=stats["steps_total"],
        kernels=common.kernels_of(stats["steps"]),
    )


inputs = spmv.inputs
reference = spmv.reference
control = spmv.control
check = spmv.check
floor = spmv.floor
