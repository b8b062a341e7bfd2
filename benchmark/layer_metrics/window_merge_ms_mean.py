"""The tables' host numpy for each engine window (``server.window``):
``server.table.add_run.merge`` (validation, stacking, ``np.unique``,
padding) plus ``server.table.get.prepare`` (id checks). Layer: tables.
Moves ``table_rows_per_s``."""

from benchmark.harness import spans


def read(run):
    return spans.per_ms(run.trace, "server.window",
                        "server.table.add_run.merge",
                        "server.table.get.prepare")
