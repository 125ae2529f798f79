"""Device ms per operation in collectives; nothing to read without any."""


def read(r):
    if r.trace is None or not r.trace["class_s"]["collective"]:
        return None
    return r.trace["class_s"]["collective"] / r.n_ops * 1e3
