"""Collective operations' share of the busiest chip's busy time (own
seconds of all-reduce and its kin over the own seconds of every operation
on the chip that was busy longest): what the exchange of rows between the
shards costs the chip the step waits for. ``collective_busy_pct`` is the
same share summed over the chips, for the table cells. 0 on one chip.
Layer: row ops and kernels. Moves ``train_items_per_s``."""


def read(run):
    s = run.trace_summary()
    if s is None:
        return None
    busiest = max(s["devices"], key=lambda d: d["busy_s"])
    own = sum(busiest["by_category_s"].values())
    if not own:
        return None
    return 100.0 * busiest["by_category_s"].get("collective", 0.0) / own
