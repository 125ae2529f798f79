"""Seconds from the first call into the program to the end of warm-up."""


def read(r):
    return r.setup["setup_s"]
