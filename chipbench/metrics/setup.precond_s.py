"""Host seconds building the block-Jacobi preconditioner: self time of
the span ``cb.block_jacobi``."""
from chipbench import program_obs


def read(r):
    return program_obs.span_self_s("cb.block_jacobi")
