"""Rows a sparse Get returned, mean over the window's Gets: the counter
``table.sparse.get.rows`` (stale rows shipped; a Get that found none ships
row 0 and counts under ``table.sparse.get.empty``) over the Gets the
workers issued. Nothing where the program has no such counter or the
runner issues no sparse Get. Layer: tables. Moves ``table_rows_per_s``."""

from benchmark.harness import program


def read(run):
    gets = run.window.get("gets")
    rows = program.counter_delta(run.counters_before, run.counters_after,
                                 "table.sparse.get.rows")
    if not gets or rows is None:
        return None
    return rows / gets
