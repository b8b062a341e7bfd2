"""Seconds of set-up that JAX's backend took to hand over the programs
asked of it, compiled or loaded from the persistent cache: the sum of the
histogram ``jit.backend_s`` (``telemetry/startup.py``: one sample a
program, the outermost phase of a thread) in the snapshot taken at
set-up's end. ``ledger`` serves the other ``setup_jit_*`` readers: nothing
where the snapshot has no compile ledger (no ``jit.backend_s``), 0 where
it has one and the histogram asked for is not there yet (a cold run loads
nothing from the cache). Layer: entry points. Moves ``setup_s``."""


def ledger(snapshot: dict, name: str, field: str = "sum"):
    """``field`` (``sum`` or ``count``) of the ledger's histogram ``name``."""
    if "jit.backend_s" not in snapshot:
        return None
    return float(snapshot.get(name, {}).get(field, 0.0))


def read(run):
    return ledger(run.counters_before, "jit.backend_s")
