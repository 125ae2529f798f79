"""Host seconds of ``from_coo``'s intra-block aggregation (span
``cb.from_coo.aggregate``)."""
from chipbench import program_obs


def read(r):
    return program_obs.span_self_s("cb.from_coo.aggregate")
