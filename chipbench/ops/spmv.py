"""y = A @ x through ``kernels.ops.cb_spmv`` on packed super-streams."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import common, floor as floor_mod


def setup(data, traffic, devices, clock):
    from repro.core import CBMatrix
    from repro.core.streams import build_super_streams
    from repro.kernels import ops

    dev = devices[0]
    with clock("from_coo"):
        cb = CBMatrix.from_coo(data.rows, data.cols, data.vals, data.shape,
                               block_size=traffic["block_size"])
    with clock("pack"):
        streams = jax.block_until_ready(
            jax.device_put(build_super_streams(cb), dev))
    stats = ops.spmv_launch_stats(streams)
    return common.Setup(
        step=jax.jit(lambda s, x: ops.cb_spmv(s, x)),
        args=(streams,),
        put=lambda x: jax.device_put(jnp.asarray(x), dev),
        output=lambda y: y,
        grid_steps=stats["steps_total"],
        kernels=common.kernels_of(stats["steps"]),
    )


def inputs(data, traffic, rng):
    """Positive vectors, as the iterates of PageRank's inner step are."""
    n = data.shape[1]
    return [rng.random(n, dtype=np.float64).astype(np.float32)
            for _ in range(traffic["inputs"])]


def reference(data, traffic, inp):
    return data.csr64() @ inp.astype(np.float64)


def control(data, traffic, inp):
    """bfloat16 values, x and products, accumulated in float32."""
    bf = jnp.bfloat16
    vals = jnp.asarray(data.vals).astype(bf)
    x = jnp.asarray(inp).astype(bf)
    prod = (vals * x[jnp.asarray(data.cols)]).astype(jnp.float32)
    y = jax.ops.segment_sum(prod, jnp.asarray(data.rows),
                            num_segments=data.shape[0])
    return np.asarray(y, np.float64)


def check(out, ref):
    return {"y_err": common.rel_max_err(out, ref)}


def floor(data, traffic):
    return floor_mod.spmv(data.counts(), data.vals.dtype.itemsize)
