"""Share of device busy time spent in collectives (all-reduce, all-gather,
collective-permute, reduce-scatter, all-to-all), summed over the chips.
Layer: row ops and kernels, across chips. Moves ``table_rows_per_s``."""

from benchmark.layer_metrics.custom_call_busy_pct import share


def read(run):
    return share(run, "collective")
