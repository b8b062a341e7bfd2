"""Largest less smallest busy seconds over the chips, over the largest:
near 0 where every chip does the same work in a step (shared evenly or
repeated on each), near the hot shard's excess where work follows the
rows. Nothing to read on one chip. Layer: device. Moves
``train_items_per_s``."""


def read(run):
    s = run.trace_summary()
    if s is None or len(s["devices"]) < 2:
        return None
    busy = [d["busy_s"] for d in s["devices"]]
    if not max(busy):
        return None
    return 100.0 * (max(busy) - min(busy)) / max(busy)
