"""``server.table.sparse.get.select`` over the window's wall, in percent:
the share of the cell the freshness plane's selection takes on the engine's
thread (42 % when it was a scan of a ``(workers, rows)`` bit matrix,
ledger, PR 30). Nothing where the program records no such span. Layer:
tables. Moves ``table_rows_per_s``."""

from benchmark.harness import spans


def read(run):
    return spans.share_pct(run.trace, "server.table.sparse.get.select")
