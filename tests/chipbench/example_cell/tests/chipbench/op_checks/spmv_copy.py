"""Faults and traced metrics of the ``spmv_copy`` operation."""
from chipbench_faults import halve

TRACED = {"setup.compile_s", "launch.grid_steps"}


def scaled(patch):
    """Every answer 0.1% too large."""
    from repro.kernels import ops

    spmv = ops.cb_spmv
    patch(ops, "cb_spmv", lambda s, x, **kw: spmv(s, x, **kw) * 1.001)


def half(patch):
    """Half of the stream groups left out, the rest doubled."""
    from repro.kernels import ops

    spmv = ops.cb_spmv
    patch(ops, "cb_spmv", lambda s, x, **kw: spmv(halve(s), x, **kw))


FAULTS = {"scaled": scaled, "half": half}
