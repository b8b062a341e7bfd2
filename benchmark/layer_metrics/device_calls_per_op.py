"""Programs the table layer launched, an operation the runner attempted (a
step, a round, a verb): the program's counter ``table.device.calls`` over
the window's ``attempted``. It counts the jitted row programs and the
eager ones around them alike (a slice of a gather's bucket, a pad of a
short delta, a reshape). Nothing to read where the program has no such
counter. Layer: row ops and kernels. Moves ``table_rows_per_s``."""

from benchmark.harness import crossings


def read(run):
    return crossings.per_op(run, "table.device.calls")
