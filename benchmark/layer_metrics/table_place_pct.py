"""Share of the traced window's wall in the table layer's copies into the
device: every span under ``server.`` whose name ends in ``.place``
(threads add up). Nothing where the program records no such span. Layer:
row ops and kernels. Moves ``table_rows_per_s``."""

from benchmark.harness import crossings


def read(run):
    return crossings.share_pct(run.trace, ".place")
