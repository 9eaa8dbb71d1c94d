"""Largest ``peak_bytes_in_use`` over the cell's devices, read after the
window and before the reference runs, in GiB."""


def read(rec):
    return rec["peak_bytes"] / 2**30
