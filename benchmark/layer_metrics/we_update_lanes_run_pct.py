"""Share of the row lanes the touched-rows AdaGrad steps laid out for their
two tables' updates that the updates ran: the program's counters
``we.update.lanes.run`` over ``we.update.lanes.laid_out`` in the window, in
percent. A step's update has a lane an id of the batch's input and output
id vectors; the ids it deduplicates are the first of those lanes and the
rest name the trash row. The update walks the first lanes in chunks of one
batch's pairs and stops after the last chunk that holds a distinct row, a
trip count the device reads; the block program sums the chunks run times
their lanes into its stats array, and the host counts the lanes the steps
run laid out. 100 says every update ran every lane (the per-shard step of
more than one chip does); the closer to the share of lanes that hold a
distinct row, the less the device spends on the trash row. Nothing to read
where the program has no such counter, or took a step that has no lanes
(the dense small-vocabulary step). Layer: updaters and fused steps. Moves
``train_items_per_s``."""

from benchmark.harness import program


def read(run):
    ran, laid_out = (program.counter_delta(
        run.counters_before, run.counters_after, "we.update.lanes." + part)
        for part in ("run", "laid_out"))
    if not laid_out or ran is None:
        return None
    return 100.0 * ran / laid_out
