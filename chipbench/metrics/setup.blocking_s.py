"""Host seconds blocking the matrix in ``CBMatrix.from_coo``: self time of
its spans ``cb.from_coo.partition`` (both partitions), ``.colagg`` and
``.formats``."""
from chipbench import program_obs


def read(r):
    return program_obs.span_self_s("cb.from_coo.partition",
                                   "cb.from_coo.colagg",
                                   "cb.from_coo.formats")
