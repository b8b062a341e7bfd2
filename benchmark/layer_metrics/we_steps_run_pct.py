"""Share of the block program's laid-out steps that its loop ran: the
program's counters ``we.block.steps.run`` over ``we.block.steps.laid_out``
in the window, in percent. A block's tokens are padded to a bucket of
lanes and its lane-batches to a bucket of steps (``nb``, a shape of the
compiled program); the loop runs the batches that hold a pair, which the
program counts from its mask and returns in the block's stats array. 100
would say every laid-out step ran; the closer to the share of steps that
hold a pair, the less the device spends on padding. Nothing to read where
the program has no such counter. Layer: updaters and fused steps. Moves
``train_items_per_s``."""

from benchmark.harness import program


def read(run):
    ran, laid_out = (program.counter_delta(
        run.counters_before, run.counters_after, "we.block.steps." + part)
        for part in ("run", "laid_out"))
    if not laid_out or ran is None:
        return None
    return 100.0 * ran / laid_out
