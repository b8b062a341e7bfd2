"""Mean length of an engine window while the application trains:
``engine_window_ms_mean``'s reading (what ``server.window.latency_s``
gained in sum over what it gained in count in the window of the run, both
exact) in a cell whose throughput is ``train_items_per_s``: a window here
holds a block's Gets or Adds of up to a million rows a table. Layer:
worker verbs and engine. Moves ``train_items_per_s``."""

from benchmark.layer_metrics.engine_window_ms_mean import read  # noqa: F401
