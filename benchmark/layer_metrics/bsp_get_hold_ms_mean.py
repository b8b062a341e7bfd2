"""Milliseconds a Get of the window was held in the BSP server's get
cache, mean over ALL the window's Gets: the seconds of the program's
spans ``server.bsp.get_hold`` (from a Get entering the cache, because its
worker has added to a round that other workers have not, to the start of
its service in the drain that the round's last Add sets off) over the
counter ``server.bsp.gets``. What the barrier costs a Get inside the
server; a Get that was never cached counts 0, so workers in lock step,
whose Gets queue in the mailbox behind the round's Adds and find the
round complete, read 0.0 and not nothing. Nothing where the program has
no such counter (any tree before PR 50) or the run was not traced. Layer:
worker verbs and engine. Moves ``table_rows_per_s``."""

from benchmark.harness import program, spans


def read(run):
    gets = program.counter_delta(run.counters_before, run.counters_after,
                                 "server.bsp.gets")
    if not gets or run.trace is None:
        return None
    held_s = spans.total_s(run.trace, "server.bsp.get_hold") or 0.0
    return 1e3 * held_s / gets
