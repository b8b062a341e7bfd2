"""Device dispatches the engine made for Adds (``server.add.dispatches``)
over the Adds the workers issued: under 1 where windows merge Adds.
Nothing where the runner issues no host-plane Add. Layer: tables. Moves
``table_rows_per_s``."""

from benchmark.harness import program


def read(run):
    adds = run.window.get("adds")
    dispatches = program.counter_delta(
        run.counters_before, run.counters_after, "server.add.dispatches")
    if not adds or dispatches is None:
        return None
    return dispatches / adds
