"""Milliseconds a step spends on the host making the pooled verbs'
segments: the program's spans ``server.table.device_fetch_pooled.prepare``
(the checks of lengths and ids, the position-to-bag map, padding) and
``server.table.device_apply_pooled.prepare`` (the same, ``np.unique`` and
the inverse map of the repeats within and across bags), summed over the
traced window's verbs and divided by its ``bench.step`` spans. What the
jaggedness costs the host before anything is copied. Nothing to read where
the program has no such span. Layer: tables. Moves ``table_rows_per_s``."""

from benchmark.harness import spans


def read(run):
    return spans.per_ms(run.trace, "bench.step",
                        "server.table.device_fetch_pooled.prepare",
                        "server.table.device_apply_pooled.prepare")
