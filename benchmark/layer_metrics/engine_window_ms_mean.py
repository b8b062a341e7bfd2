"""Mean length of an engine window during the window of the run: what
``server.window.latency_s`` gained in sum over what it gained in count,
both exact (a quantile off the program's octave ladder can be an octave
off, too coarse to see a change). Layer: worker verbs and engine. Moves
``table_rows_per_s``."""

from benchmark.harness import program


def read(run):
    gained = program.histogram_delta(
        run.counters_before, run.counters_after, "server.window.latency_s")
    if gained is None or not gained[0]:
        return None
    return 1e3 * gained[1] / gained[0]
