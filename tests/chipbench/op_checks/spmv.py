"""Faults and traced metrics of the ``spmv`` operation (``chipbench/ops/spmv.py``)."""
from chipbench_faults import halve

TRACED = {"setup.from_coo_s", "setup.pack_s", "setup.compile_s",
          "launch.grid_steps"}


def altered(patch):
    """The answer altered where it is produced."""
    from repro.kernels import ops

    spmv = ops.cb_spmv
    patch(ops, "cb_spmv", lambda s, x, **kw: spmv(s, x, **kw).at[0].add(1.0))


def half(patch):
    """Half of the stream groups left out, the rest doubled."""
    from repro.kernels import ops

    spmv = ops.cb_spmv
    patch(ops, "cb_spmv", lambda s, x, **kw: spmv(halve(s), x, **kw))


FAULTS = {"altered": altered, "half": half}
