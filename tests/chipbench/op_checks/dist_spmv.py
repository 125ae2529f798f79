"""Faults and traced metrics of the ``dist_spmv`` operation
(``chipbench/ops/dist_spmv.py``): those of ``spmv``, whose kernel each chip
runs on its shard, and the exchange between chips left out."""
import jax

from chipbench_faults import load_checks

spmv = load_checks("spmv")
TRACED = spmv.TRACED


def no_exchange(patch):
    """The reduce-scatter between chips left out: each chip keeps its own
    partial sums."""
    def local(y, axis, scatter_dimension=0, tiled=True):
        n = y.shape[0] // jax.lax.axis_size(axis)
        return jax.lax.dynamic_slice_in_dim(y, jax.lax.axis_index(axis) * n, n)

    patch(jax.lax, "psum_scatter", local)


FAULTS = {"no_exchange": no_exchange, "altered": spmv.altered, "half": spmv.half}
