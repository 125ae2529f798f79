"""Host seconds packing and placing the operands (streams, sharding,
block-Jacobi, ``device_put``)."""


def read(r):
    return r.setup.get("pack_s")
