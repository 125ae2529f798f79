"""``peak_bytes_in_use`` after the window, on the fullest chip, in MiB."""


def read(r):
    return None if r.peak_bytes is None else r.peak_bytes / 2**20
