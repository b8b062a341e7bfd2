"""Seconds of set-up inside ``MV_Init``: the gauge ``mv.init_s`` (all of
``Zoo.Start``: flags, mesh, engine, planes), as it stands at set-up's
end. Nothing to read where the program has no such gauge. Layer: entry
points. Moves ``setup_s``."""


def read(run):
    gauge = run.counters_before.get("mv.init_s")
    return None if gauge is None else float(gauge["value"])
