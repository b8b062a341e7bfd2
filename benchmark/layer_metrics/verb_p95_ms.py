"""95th percentile (nearest rank) of one blocking client verb in the
window; the sample count and the quantiles of Adds and Gets apart are on
an earlier line of the run. It was drawn as the end-to-end metric
``op_p95_ms``; its spread from run to run (5.9 % of the median over six
runs on the chip, PR 22) cannot carry a bound within the contract's 10 %,
so it is read here. Layer: worker verbs and engine. Moves
``table_rows_per_s``."""

from benchmark.harness import clock


def read(run):
    ops = run.window.get("op_ms")
    return clock.percentile(ops, 95) if ops else None
