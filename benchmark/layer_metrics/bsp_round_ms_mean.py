"""Mean length of a BSP round during the window of the run: what the
program's ``server.bsp.round_s`` gained in sum over what it gained in
count, both exact. A round there runs from the admission of the first
Add of a round to the reply of its last Get, on the engine's thread: in
lock step it is the whole of a round but the workers' own time between a
reply and their next send. Nothing where the program has no such
instrument (any tree before PR 50) or the server is not the BSP one.
Layer: worker verbs and engine. Moves ``table_rows_per_s``."""

from benchmark.harness import program


def read(run):
    gained = program.histogram_delta(
        run.counters_before, run.counters_after, "server.bsp.round_s")
    if gained is None or not gained[0]:
        return None
    return 1e3 * gained[1] / gained[0]
