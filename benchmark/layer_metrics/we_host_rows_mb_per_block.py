"""Row bytes that cross between the server and the worker a block, in MB:
the program's counters ``we.host_plane.fetched_bytes`` (what a block's
Gets return) plus ``we.host_plane.pushed_bytes`` (what its Adds send) over
``we.blocks`` in the window. Four tables, two directions, 512 bytes a row:
the bytes the host plane moves that the device planes do not. Nothing to
read where the program has no such counters. Layer: tables. Moves
``train_items_per_s``."""

from benchmark.harness import program


def read(run):
    fetched, pushed, blocks = (program.counter_delta(
        run.counters_before, run.counters_after, name)
        for name in ("we.host_plane.fetched_bytes",
                     "we.host_plane.pushed_bytes", "we.blocks"))
    if fetched is None or pushed is None or not blocks:
        return None
    return (fetched + pushed) / blocks / 1e6
