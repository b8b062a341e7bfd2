"""Share of the traced window's wall that the worker stood waiting for a
block's rows: the sum of ``worker.we.fetch`` over the window. On the host
plane's block pipeline (``-is_pipeline 1``) that span is the wait for the
prefetched Gets' reply (after a block's push, before the next block) and
the first block's own blocking fetch: what of the rows' way through the
engine the prefetch did not hide. Layer: app loop. Moves ``train_items_per_s``."""

from benchmark.harness import spans


def read(run):
    return spans.share_pct(run.trace, "worker.we.fetch")
