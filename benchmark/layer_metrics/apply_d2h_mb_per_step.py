"""Megabytes (1e6 bytes) of delta that ``device_apply_rows`` copied from the
device to the host to combine repeated ids, a step: the program's counter
``table.device_apply.d2h_bytes`` over the window's steps. 0 where repeats
combine on the device; 268 a step of 32,768 positions x 2,048 float32 where
they do not. Nothing to read where the program has no such counter. Layer:
tables. Moves ``table_rows_per_s``."""

from benchmark.harness import program


def read(run):
    moved = program.counter_delta(run.counters_before, run.counters_after,
                                  "table.device_apply.d2h_bytes")
    steps = run.window.get("attempted")
    if moved is None or not steps:
        return None
    return moved / 1e6 / steps
