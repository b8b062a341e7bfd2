"""Seconds of set-up that JAX spent tracing programs: the sum of the
histogram ``jit.trace_s`` (one sample a program; the jitted functions a
program calls while it is traced are inside its sample, not beside it) in
the snapshot taken at set-up's end. A program served by the persistent
cache is traced all the same. Layer: entry points. Moves ``setup_s``."""

from benchmark.layer_metrics.setup_jit_backend_s import ledger


def read(run):
    return ledger(run.counters_before, "jit.trace_s")
