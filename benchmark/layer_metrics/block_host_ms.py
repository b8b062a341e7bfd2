"""Mean length of ``worker.we.block``: the host's part of a block (its
fetch, upload, dispatch and push; the dispatches are asynchronous, so the
device's work is not in it). Layer: app loop. Moves
``train_items_per_s``."""

from benchmark.harness import spans


def read(run):
    return spans.mean_ms(run.trace, "worker.we.block")
