"""Share of the traced window's wall in the device-plane verbs' copies
and program calls: ``server.table.device_fetch.dispatch`` plus
``server.table.device_apply.dispatch``. Layer: tables. Moves
``table_rows_per_s``."""

from benchmark.harness import spans


def read(run):
    return spans.share_pct(run.trace, "server.table.device_fetch.dispatch",
                           "server.table.device_apply.dispatch")
