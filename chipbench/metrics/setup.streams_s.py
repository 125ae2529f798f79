"""Host seconds building the kernels' streams: self time of the spans
``cb.streams.collect``, ``cb.streams.layout``, ``cb.shard.build`` and
``cb.shard.stack``."""
from chipbench import program_obs


def read(r):
    return program_obs.span_self_s("cb.streams.collect",
                                   "cb.streams.layout",
                                   "cb.shard.build",
                                   "cb.shard.stack")
