"""Seconds spent creating the tables: the sum of the histogram
``table.create_s`` (one sample a table: initialiser, storage layout,
placement), as it stands after the window (set-up ends before the first
snapshot). Layer: entry points. Moves ``setup_s``."""


def read(run):
    made = run.counters_after.get("table.create_s")
    if made is None or not made.get("count"):
        return None
    return float(made["sum"])
