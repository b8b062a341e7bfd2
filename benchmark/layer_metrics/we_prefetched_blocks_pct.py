"""Share of the window's blocks that trained on prefetched rows: the
program's counters ``we.pipeline.prefetched_blocks`` over ``we.blocks`` in
the window, in percent. The first block of a ``train()`` fetches its own
rows, every other one finds them asked for a block earlier: 66.7 in a
window of one pass of three blocks. Nothing to read where the program has
no such counter. Layer: app loop. Moves ``train_items_per_s``."""

from benchmark.harness import program


def read(run):
    prefetched, blocks = (program.counter_delta(
        run.counters_before, run.counters_after, name)
        for name in ("we.pipeline.prefetched_blocks", "we.blocks"))
    if prefetched is None or not blocks:
        return None
    return 100.0 * prefetched / blocks
