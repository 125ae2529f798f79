"""Whole measured window over the operations completed in it, in ms."""


def read(r):
    return r.window_s / r.n_ops * 1e3
