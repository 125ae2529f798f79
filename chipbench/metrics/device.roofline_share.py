"""The floor work's least time over the device-busy time of one
operation, in %. The least time is the larger of floor bytes over the
chips' HBM bandwidth and floor operations over their peak
(``chipbench.floor``, ``chipbench.peaks``)."""


def read(r):
    if r.trace is None or r.least_time_s is None or not r.trace["busy_s"]:
        return None
    return 100.0 * r.least_time_s / (r.trace["busy_s"] / r.n_ops)
