"""Seconds of set-up that JAX spent lowering traced programs to MLIR
modules: the sum of the histogram ``jit.lower_s`` (one sample a program)
in the snapshot taken at set-up's end. A program served by the persistent
cache is lowered all the same: its module is the cache's key. Layer: entry
points. Moves ``setup_s``."""

from benchmark.layer_metrics.setup_jit_backend_s import ledger


def read(run):
    return ledger(run.counters_before, "jit.lower_s")
