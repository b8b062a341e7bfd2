"""``device_idle_pct`` in the cells whose throughput is
``table_rows_per_s``."""

from benchmark.layer_metrics.device_idle_pct import read  # noqa: F401
