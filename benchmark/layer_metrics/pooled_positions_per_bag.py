"""Positions pooled for each pooled row returned: the program's counters
``table.device_fetch_pooled.positions`` over ``table.device_fetch_pooled.
bags``, as they moved over the window: the jaggedness the traffic really
had. 8.2 for the source's whole bags (214 ids a sample in 26); a
row-sharded server of 32 meets partial bags, about 1.68 (1.0 for a one-hot
table, 3.27 for the 100-hot one). Nothing to read where the program has no
such counter (any before the pooled verbs). Layer: tables. Moves
``table_rows_per_s``."""

from benchmark.harness import program


def read(run):
    positions, bags = (program.counter_delta(
        run.counters_before, run.counters_after,
        f"table.device_fetch_pooled.{what}")
        for what in ("positions", "bags"))
    if not positions or not bags:
        return None
    return positions / bags
