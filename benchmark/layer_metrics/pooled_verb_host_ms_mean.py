"""Mean host milliseconds of a pooled device-plane verb: the program's
spans ``server.table.device_fetch_pooled`` and
``server.table.device_apply_pooled`` (length and id checks, the bag map,
``np.unique`` and the inverse map, padding, the one copy of the small int
vectors and the call of the one program; the device runs behind them),
over the verbs of the traced window. ``device_verb_host_ms_mean``'s
counterpart, whose reader names the row verbs' spans. Nothing to read
where the program has no such span. Layer: tables. Moves
``table_rows_per_s``."""

from benchmark.harness import spans

VERBS = ("server.table.device_fetch_pooled",
         "server.table.device_apply_pooled")


def read(run):
    n, secs = spans.count(run.trace, *VERBS), spans.total_s(run.trace, *VERBS)
    if not n or secs is None:
        return None
    return 1e3 * secs / n
