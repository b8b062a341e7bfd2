"""Milliseconds of drain a round: the seconds of the program's spans
``server.bsp.drain`` (the loop on the BSP server's thread that serves the
cached Gets, one blocking gather and copy back after another, when a
round's last Add has landed, and the one that applies the cached Adds
when a round's last Get has been served; an empty cache opens no span)
over the add rounds the window completed (counter ``server.bsp.rounds``).
0.0 where no verb was cached in the window. Nothing where the program has
no such counter (any tree before PR 50) or the run was not traced. Layer:
worker verbs and engine. Moves ``table_rows_per_s``."""

from benchmark.harness import program, spans


def read(run):
    rounds = program.counter_delta(run.counters_before, run.counters_after,
                                   "server.bsp.rounds")
    if not rounds or run.trace is None:
        return None
    drain_s = spans.total_s(run.trace, "server.bsp.drain") or 0.0
    return 1e3 * drain_s / rounds
