"""Host seconds in the Alg. 2 load balancers: spans ``cb.from_coo.balance``,
``cb.streams.balance`` and ``cb.shard.balance`` (self time)."""
from chipbench import program_obs


def read(r):
    return program_obs.span_self_s("cb.from_coo.balance",
                                   "cb.streams.balance",
                                   "cb.shard.balance")
