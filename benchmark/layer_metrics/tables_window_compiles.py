"""``window_compiles`` in the cells whose throughput is
``table_rows_per_s``."""

from benchmark.layer_metrics.window_compiles import read  # noqa: F401
