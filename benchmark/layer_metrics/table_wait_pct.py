"""Share of the traced window's wall the table layer waits for the device
before a copy back: every span under ``server.`` whose name ends in
``.wait`` (``block_until_ready`` of what ``.take`` then copies; recorded
only while ``-trace`` is on). Nothing where the program records no such
span. Layer: row ops and kernels. Moves ``table_rows_per_s``."""

from benchmark.harness import crossings


def read(run):
    return crossings.share_pct(run.trace, ".wait")
