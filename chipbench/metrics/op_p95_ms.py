"""95th percentile of every operation latency in the window, in ms
(``statistics.quantiles``, exclusive method)."""


def read(r):
    import statistics

    if len(r.latencies_s) < 20:
        return None
    return statistics.quantiles(r.latencies_s, n=20)[18] * 1e3
