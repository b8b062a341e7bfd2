"""Mean length of ``server.table.sparse.get.select``: the host's choice of
which rows a sparse Get ships (the drain of the worker's dirty id set: one
``np.unique`` over the ids marked since its last Get). Nothing where the
program records no such span. Layer: tables. Moves ``table_rows_per_s``."""

from benchmark.harness import spans


def read(run):
    return spans.mean_ms(run.trace, "server.table.sparse.get.select")
