"""Programs asked of JAX's backend during set-up, compiled or loaded from
the persistent cache: the count of the histogram ``jit.backend_s`` in the
snapshot taken at set-up's end (``setup_compiled_programs`` counts the ones
the cache did not serve, from outside the program). Layer: entry points.
Moves ``setup_s``."""

from benchmark.layer_metrics.setup_jit_backend_s import ledger


def read(run):
    return ledger(run.counters_before, "jit.backend_s", "count")
