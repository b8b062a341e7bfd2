"""Share of the traced window's wall that the train loop spent waiting
for the loader: the sum of ``worker.we.pop_wait`` over the window.
Dispatch is asynchronous, so this is the loop's wait; ``breakdown``'s
``idle_gaps`` says how much of it the chip felt. Layer: app loop. Moves
``train_items_per_s``."""

from benchmark.harness import spans


def read(run):
    return spans.share_pct(run.trace, "worker.we.pop_wait")
