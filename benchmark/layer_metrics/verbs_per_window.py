"""Verbs the engine admitted (``server.window.verbs``) over the engine
windows it formed (the count of ``server.window.latency_s``, which sees
every window; ``digest.engine.window_s`` samples one in a few in a
single-process world) during the window: how far the engine batches what
the workers send. Nothing where the cell bypasses the engine. Layer:
worker verbs and engine. Moves ``table_rows_per_s``."""

from benchmark.harness import program


def read(run):
    verbs = program.counter_delta(run.counters_before, run.counters_after,
                                  "server.window.verbs")
    windows = program.histogram_delta(
        run.counters_before, run.counters_after, "server.window.latency_s")
    if not verbs or windows is None or not windows[0]:
        return None
    return verbs / windows[0]
