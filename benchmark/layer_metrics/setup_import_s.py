"""Seconds of set-up spent importing the training plane: the gauge
``mv.import_s`` (``telemetry/startup.py``: the package's lazy import,
api -> zoo -> jax, and ``compile_cache.enable()``, where the harness first
imports jax), as it stands at set-up's end. Nothing to read where the
program has no such gauge. Layer: entry points. Moves ``setup_s``."""


def read(run):
    gauge = run.counters_before.get("mv.import_s")
    return None if gauge is None else float(gauge["value"])
