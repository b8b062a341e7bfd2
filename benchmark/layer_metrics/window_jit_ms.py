"""Milliseconds that JAX spent tracing, lowering and compiling or loading
programs inside the window: what the histograms ``jit.trace_s``,
``jit.lower_s`` and ``jit.backend_s`` (``telemetry/startup.py``) gained in
sum between the two snapshots. Must be 0 where the warm-up met every
shape; unlike ``window_compiles`` it also sees a program that is traced
again and served by the cache. Nothing to read where the program has no
compile ledger. Layer: entry points. Moves the cell's throughput."""

from benchmark.harness import program

PHASES = ("jit.trace_s", "jit.lower_s", "jit.backend_s")


def read(run):
    gained = [program.histogram_delta(run.counters_before,
                                      run.counters_after, name)
              for name in PHASES]
    if all(g is None for g in gained):
        return None
    return 1e3 * sum(g[1] for g in gained if g is not None)
