"""Megabytes (1e6 bytes) of host arrays the table layer copied into the
device (ids, inverse maps, option scalars, host deltas), an operation the
runner attempted: the program's counter ``table.device.h2d_bytes`` over
the window's ``attempted``. A host array counts once, whatever the
sharding replicates. Nothing to read where the program has no such
counter. Layer: row ops and kernels. Moves ``table_rows_per_s``."""

from benchmark.harness import crossings


def read(run):
    return crossings.per_op(run, "table.device.h2d_bytes", 1e-6)
