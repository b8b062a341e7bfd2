"""``top_op_busy_pct`` in the cells whose throughput is
``table_rows_per_s``. Layer: row ops and kernels."""

from benchmark.layer_metrics.top_op_busy_pct import read  # noqa: F401
