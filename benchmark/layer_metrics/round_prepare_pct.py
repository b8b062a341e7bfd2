"""Share of the traced window's wall in the device-plane verbs' host
numpy: ``server.table.device_fetch.prepare`` plus
``server.table.device_apply.prepare`` (id checks, ``np.unique``,
padding). Layer: tables. Moves ``table_rows_per_s``."""

from benchmark.harness import spans


def read(run):
    return spans.share_pct(run.trace, "server.table.device_fetch.prepare",
                           "server.table.device_apply.prepare")
