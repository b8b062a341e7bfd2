"""The few places where the harness touches the program besides driving
it: its compile cache, its counters and the bridge that puts its spans
into the profiler's trace. Runners import the system under test
themselves."""

from __future__ import annotations


def enable_compile_cache() -> str:
    """The program's own helper: ``JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_cache``, a fixed path inside the checkout."""
    from multiverso_tpu.utils import compile_cache
    return compile_cache.enable()


def metrics_snapshot() -> dict:
    """This process's instruments, as ``MV_MetricsSnapshot()`` gives them
    in a single-process world (and without its collective)."""
    from multiverso_tpu.telemetry import metrics
    return metrics.snapshot()


def bridge_spans(on: bool) -> None:
    """What ``MV_StartProfiler`` / ``MV_StopProfiler`` do besides starting
    the trace (which the harness does itself, to turn the Python tracer
    off): the program's spans enter the trace as TraceAnnotations. Needs a
    running world, because ``-trace`` is a flag of the world."""
    import multiverso_tpu as mv
    from multiverso_tpu.telemetry import trace as ttrace
    mv.MV_SetFlag("trace", bool(on))
    ttrace.set_xplane(bool(on))
    if not on:
        ttrace.clear()


def counter_delta(before: dict, after: dict, name: str):
    """How far counter ``name`` moved between two snapshots, None if it is
    not there."""
    if name not in after:
        return None
    return (float(after[name].get("value", 0.0))
            - float(before.get(name, {}).get("value", 0.0)))


def histogram_delta(before: dict, after: dict, name: str):
    """(samples, their sum) that histogram or digest ``name`` gained
    between two snapshots, None if it is not there. Both are exact, unlike
    a quantile read off the octave ladder."""
    if name not in after:
        return None
    old = before.get(name, {})
    return (int(after[name].get("count", 0)) - int(old.get("count", 0)),
            float(after[name].get("sum", 0.0)) - float(old.get("sum", 0.0)))
