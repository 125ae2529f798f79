"""Pallas grid steps of one matvec, summed over formats
(``kernels.ops.spmv_launch_stats``); per device on a mesh."""


def read(r):
    return r.grid_steps
