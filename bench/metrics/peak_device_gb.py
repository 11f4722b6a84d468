"""Largest ``peak_bytes_in_use`` over the cell's devices after the
window, in GB (1e9 bytes)."""


def read(run):
    return None if run.peak_bytes is None else run.peak_bytes / 1e9
