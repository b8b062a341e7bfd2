"""Share of the hierarchical-softmax output lanes that lie on a path: the
program's counters ``we.hs.path_lanes.valid`` over ``.padded`` in the
window, in percent. The block program lays out the longest code's lanes
for every token and masks what lies past a word's own path; the masked
lanes are gathered, multiplied, sorted and written like the others.
Nothing to read where the program has no such counter. Layer: updaters
and fused steps. Moves ``train_items_per_s``."""

from benchmark.harness import program


def read(run):
    valid, padded = (program.counter_delta(
        run.counters_before, run.counters_after, "we.hs.path_lanes." + part)
        for part in ("valid", "padded"))
    if not padded:
        return None
    return 100.0 * (valid or 0.0) / padded
