"""Milliseconds a step spends handling repeated ids on the host before its
applies are dispatched: the program's span
``server.table.device_apply.combine`` (inside ``.device_apply.prepare``:
``np.unique``'s inverse map of a verb's ids, padded for the device's
segment-sum), summed over the traced window's verbs and divided by its
``bench.step`` spans. Nothing where no apply of the window repeats an id.
Layer: tables. Moves ``table_rows_per_s``."""

from benchmark.harness import spans


def read(run):
    return spans.per_ms(run.trace, "bench.step",
                        "server.table.device_apply.combine")
