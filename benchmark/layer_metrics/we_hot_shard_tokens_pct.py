"""Share of the window's tokens whose input row lives on the fullest
shard: the program's counters ``we.block.tokens.shard<k>`` (a block's
tokens by the shard owning their input row, counted on the host a block),
the largest over their sum. 25 on four even shards; block sharding of a
vocabulary ordered by count puts nearly every token on shard 0. Nothing to
read where the program has no such counter. Layer: app loop. Moves
``train_items_per_s``."""

from benchmark.harness import program

PREFIX = "we.block.tokens.shard"


def read(run):
    moved = [program.counter_delta(run.counters_before, run.counters_after,
                                   name) or 0.0
             for name in run.counters_after if name.startswith(PREFIX)]
    if not sum(moved):
        return None
    return 100.0 * max(moved) / sum(moved)
