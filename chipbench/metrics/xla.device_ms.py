"""Device ms per operation in XLA ops other than Pallas kernels and
collectives (gathers, the scatter-add combine, vector work)."""


def read(r):
    if r.trace is None:
        return None
    return r.trace["class_s"]["xla"] / r.n_ops * 1e3
