"""The tables' host-to-device copies and program calls for each engine
window (``server.window``): ``server.table.add_run.dispatch`` plus
``server.table.get.dispatch``. Layer: tables. Moves
``table_rows_per_s``."""

from benchmark.harness import spans


def read(run):
    return spans.per_ms(run.trace, "server.window",
                        "server.table.add_run.dispatch",
                        "server.table.get.dispatch")
