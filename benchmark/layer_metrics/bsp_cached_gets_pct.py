"""Share of the window's Gets that the BSP server held in its get cache:
the program's counters ``server.bsp.gets_cached`` over
``server.bsp.gets``, in percent. A Get is cached when it arrives before
its round's last Add; the worker whose Add ends the round is never
cached at its Get, so W workers that keep pace read 100 (W - 1) / W: 75
for four. Nothing where the program has no such counters. Layer: worker
verbs and engine. Moves ``table_rows_per_s``."""

from benchmark.harness import program


def read(run):
    cached, gets = (program.counter_delta(
        run.counters_before, run.counters_after, name)
        for name in ("server.bsp.gets_cached", "server.bsp.gets"))
    if cached is None or not gets:
        return None
    return 100.0 * cached / gets
