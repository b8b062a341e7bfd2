"""Largest ``peak_bytes_in_use`` over the cell's devices after the window,
in GB (1e9 bytes): a capacity record. Layer: device. Listed under
``setup_s`` because creating and filling the tables is set-up work."""


def read(run):
    return run.memory_peak_bytes / 1e9 if run.memory_peak_bytes else None
