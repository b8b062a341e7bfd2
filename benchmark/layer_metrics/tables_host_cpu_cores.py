"""``host_cpu_cores`` in the cells whose throughput is
``table_rows_per_s``. Layer: worker verbs and engine."""

from benchmark.layer_metrics.host_cpu_cores import read  # noqa: F401
