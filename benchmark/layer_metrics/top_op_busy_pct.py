"""Share of device busy time of the one device operation that took most
of it (its own time, without what it nests, summed over occurrences and
chips); ``breakdown`` names it. Layer: updaters and fused steps. Moves
``train_items_per_s``."""


from benchmark.harness import trace


def read(run):
    s = run.trace_summary()
    if s is None:
        return None
    by_name = trace.own_time_by_name(s)
    total = sum(by_name.values())
    return 100.0 * max(by_name.values()) / total if total else None
