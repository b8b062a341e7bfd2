"""``custom_call_busy_pct`` in the cells whose throughput is
``table_rows_per_s``."""

from benchmark.layer_metrics.custom_call_busy_pct import read  # noqa: F401
