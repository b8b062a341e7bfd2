"""``server.window.finalize`` for each engine window (``server.window``):
the blocking device-to-host fetch of each Get and the replies. Layer:
worker verbs and engine. Moves ``table_rows_per_s``."""

from benchmark.harness import spans


def read(run):
    return spans.per_ms(run.trace, "server.window", "server.window.finalize")
