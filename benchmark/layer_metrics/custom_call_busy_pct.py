"""Share of device busy time spent in custom calls (the Mosaic kernels of
``ops/pallas_rows.py``), by the trace's HLO category, summed over the
chips. Layer: row ops and kernels. Moves ``train_items_per_s``."""


def share(run, cat: str):
    s = run.trace_summary()
    if s is None:
        return None
    own = sum(sum(d["by_category_s"].values()) for d in s["devices"])
    if not own:
        return None
    return 100.0 * sum(d["by_category_s"].get(cat, 0.0)
                       for d in s["devices"]) / own


def read(run):
    return share(run, "custom-call")
