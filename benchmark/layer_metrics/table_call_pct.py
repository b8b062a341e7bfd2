"""Share of the traced window's wall in the table layer's program
launches, jitted and eager: every span under ``server.`` whose name ends
in ``.call`` (threads add up). The launch is the host's part: the program
runs on the device after the span has ended. Nothing where the program
records no such span. Layer: row ops and kernels. Moves
``table_rows_per_s``."""

from benchmark.harness import crossings


def read(run):
    return crossings.share_pct(run.trace, ".call")
