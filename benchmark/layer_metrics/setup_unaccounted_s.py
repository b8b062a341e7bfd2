"""Seconds of set-up that no phase and no compile accounts for: the run's
``setup_s`` less the counter ``startup.phased_s`` (the outermost phases of
``telemetry/startup.py``: the import, ``MV_Init``, table creation, the
WordEmbedding app's host preparation, with the compiles inside them) less
the counter ``jit.unphased_s`` (tracing, lowering and backend seconds of
programs built outside every phase), both as the snapshot taken at
set-up's end holds them. What is left is the interpreter's start, the
harness's and the runner's own imports and data, the backend's wake-up
and the warm-up's execution. Nothing to read where the program has no
such counter. Layer: entry points. Moves ``setup_s``."""


def read(run):
    phased = run.counters_before.get("startup.phased_s")
    if phased is None:
        return None
    unphased = run.counters_before.get("jit.unphased_s", {"value": 0.0})
    return (float(run.setup_s) - float(phased["value"])
            - float(unphased["value"]))
