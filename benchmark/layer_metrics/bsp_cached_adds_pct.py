"""Share of the window's Adds that the BSP server held in its add cache:
the program's counters ``server.bsp.adds_cached`` over
``server.bsp.adds``, in percent: how often a worker whose Get was served
first is held at its next Add until the slowest worker has read. Nothing
where the program has no such counters. Layer: worker verbs and engine.
Moves ``table_rows_per_s``."""

from benchmark.harness import program


def read(run):
    cached, adds = (program.counter_delta(
        run.counters_before, run.counters_after, name)
        for name in ("server.bsp.adds_cached", "server.bsp.adds"))
    if cached is None or not adds:
        return None
    return 100.0 * cached / adds
