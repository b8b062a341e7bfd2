"""Mean length of ``server.table.sparse.get.read``: the gather of the
chosen rows at their bucket and the copy of the bucket back to the host,
blocking, on the engine's thread. Nothing where the program records no
such span. Layer: tables. Moves ``table_rows_per_s``."""

from benchmark.harness import spans


def read(run):
    return spans.mean_ms(run.trace, "server.table.sparse.get.read")
