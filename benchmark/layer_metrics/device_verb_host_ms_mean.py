"""Mean host milliseconds of a device-plane verb: the program's spans
``server.table.device_fetch`` and ``server.table.device_apply`` (id
checks, the handling of repeats, padding, the copy of the ids and the call
of the row program; the device runs behind them), over the verbs of the
traced window. A step of many tables pays it once a table and verb. Layer:
tables. Moves ``table_rows_per_s``."""

from benchmark.harness import spans

VERBS = ("server.table.device_fetch", "server.table.device_apply")


def read(run):
    n, secs = spans.count(run.trace, *VERBS), spans.total_s(run.trace, *VERBS)
    if not n or secs is None:
        return None
    return 1e3 * secs / n
