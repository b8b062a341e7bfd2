"""Share of the traced window's wall that the worker spent pushing a
block's deltas: the sum of ``worker.we.push`` over the window. On the host
plane that is the copy of the trained rows back (which waits for the
block's program; child ``.take`` where the program records it), the
subtraction (``.delta``) and the ``AddFireForget`` calls (``.add``).
Layer: app loop. Moves ``train_items_per_s``."""

from benchmark.harness import spans


def read(run):
    return spans.share_pct(run.trace, "worker.we.push")
