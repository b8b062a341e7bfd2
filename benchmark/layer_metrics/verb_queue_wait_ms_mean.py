"""Mean time a verb sat in the engine's mailbox before the engine took
it: what the ``actor.server*.queue_wait_s`` histograms (one an engine
shard) gained in sum over what they gained in count during the window.
Layer: worker verbs and engine. Moves ``table_rows_per_s``."""

from benchmark.harness import program


def read(run):
    samples, seconds = 0, 0.0
    for name in run.counters_after:
        if name.startswith("actor.server") and name.endswith(
                ".queue_wait_s"):
            n, s = program.histogram_delta(run.counters_before,
                                           run.counters_after, name)
            samples, seconds = samples + n, seconds + s
    if not samples:
        return None
    return 1e3 * seconds / samples
