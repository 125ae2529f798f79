"""Host seconds in ``CBMatrix.from_coo``."""


def read(r):
    return r.setup.get("from_coo_s")
