"""``window_jit_ms`` in the cells whose throughput is
``table_rows_per_s``."""

from benchmark.layer_metrics.window_jit_ms import read  # noqa: F401
