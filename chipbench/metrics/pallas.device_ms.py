"""Device ms per operation in the Pallas kernels (``tpu_custom_call``)."""


def read(r):
    if r.trace is None:
        return None
    return r.trace["class_s"]["pallas"] / r.n_ops * 1e3
