"""Seconds of set-up that the persistent compile cache took to find and
load the programs it served: the sum of the histogram ``jit.cache_load_s``
(JAX's own retrieval time, a part of ``jit.backend_s``) in the snapshot
taken at set-up's end; 0 in a run that the cache served nothing. Layer:
entry points. Moves ``setup_s``."""

from benchmark.layer_metrics.setup_jit_backend_s import ledger


def read(run):
    return ledger(run.counters_before, "jit.cache_load_s")
