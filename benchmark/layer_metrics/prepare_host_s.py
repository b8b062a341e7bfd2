"""Seconds of the app's ``prepare()`` on the host before the world
starts: the gauges ``we.prepare.dictionary_s`` plus
``we.prepare.sampler_s``, as they stand after the window (set-up ends
before the first snapshot, so a difference would be 0). Layer: entry
points. Moves ``setup_s``."""


def read(run):
    parts = [run.counters_after.get(f"we.prepare.{part}_s")
             for part in ("dictionary", "sampler")]
    if any(p is None for p in parts):
        return None
    return sum(float(p["value"]) for p in parts)
