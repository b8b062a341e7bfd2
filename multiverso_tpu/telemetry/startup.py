"""Where a start goes, and what JAX builds: set-up by phase, and every
program JAX traced, lowered, compiled or loaded, by name.

Two ledgers on the registry and the spans the package has
(docs/DESIGN.md §6):

* :func:`phase` times a named stretch of set-up on the calling thread
  (the lazy import, ``MV_Init`` and its parts, a table's creation, the
  WordEmbedding app's host preparation) into ``<name>_s``; the seconds of
  OUTERMOST phases add up in ``startup.phased_s``, so a phase inside a
  phase is counted once.
* :func:`listen` subscribes to JAX's own compile events. JAX reports a
  start and a duration for each of a program's three phases (tracing,
  lowering, the backend's compile or its load from the persistent cache)
  with the program's name, and reports them for every jitted function a
  program calls while it is traced. The ledger keeps the OUTERMOST phase
  of a thread: ``jit.trace_s`` / ``jit.lower_s`` / ``jit.backend_s``,
  ``jit.program.<name>.*`` by program, ``jit.unphased_s`` for what ran
  outside every :func:`phase`. With ``-trace`` on each is also a span,
  ``<innermost open span>.jit.<phase>``: a compile on the hot path shows
  under the verb that paid for it.

A listener runs only when JAX compiles, so a steady state pays nothing
for it. This module is imported before jax (the lazy import is its first
phase) and imports nothing heavy.
"""

from __future__ import annotations

import threading
import time
from typing import List

from multiverso_tpu.telemetry import metrics
from multiverso_tpu.telemetry import trace as ttrace

#: JAX's duration events (``jax._src.dispatch``) -> the ledger's phase
_JIT_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_COUNTS = {
    "/jax/compilation_cache/cache_hits": "jit.cache_hits",
    "/jax/compilation_cache/cache_misses": "jit.cache_misses",
}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_PROGRAM = "jit.program."

#: per thread: ``phases`` open :func:`phase` blocks, ``jit`` open JAX
#: phases, ``span`` / ``program`` of the outermost open JAX phase
_tls = threading.local()
_listen_lock = threading.Lock()
_listening = False


class phase:
    """``with phase("mv.init"):`` — the block's seconds go to the gauge
    ``mv.init_s`` (added: a phase may run again), or with
    ``histogram=True`` to one sample of the histogram of that name; to
    ``startup.phased_s`` too if no other phase is open on this thread.
    With ``-trace`` on the block is a span called ``name``."""

    __slots__ = ("name", "_histogram", "_span", "_t0")

    def __init__(self, name: str, histogram: bool = False):
        self.name = name
        self._histogram = histogram

    def __enter__(self):
        _tls.phases = getattr(_tls, "phases", 0) + 1
        self._span = ttrace.span(self.name)
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self._t0
        self._span.__exit__(*exc)
        _tls.phases -= 1
        if self._histogram:
            metrics.histogram(self.name + "_s").observe(seconds)
        else:
            metrics.gauge(self.name + "_s").inc(seconds)
        if not _tls.phases:
            metrics.counter("startup.phased_s").inc(seconds)
        return False


def _program_name(fun_name) -> str:
    """``jit(my_prog)`` (lowering, backend) and ``my_prog`` (tracing) are
    one program."""
    name = str(fun_name)
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]
    return name


def _on_start(event: str, _value, fun_name="", **_kw) -> None:
    kind = _JIT_PHASES.get(event)
    if kind is None:
        return
    depth = getattr(_tls, "jit", 0)
    _tls.jit = depth + 1
    if depth:
        return                  # a jitted function inside a program's phase
    _tls.program = _program_name(fun_name)
    _tls.span = ttrace.begin_child(".jit." + kind,
                                   args={"program": _tls.program})


def _on_duration(event: str, seconds: float, fun_name="", **_kw) -> None:
    kind = _JIT_PHASES.get(event)
    if kind is None:
        if event == _CACHE_LOAD:
            metrics.histogram("jit.cache_load_s").observe(seconds)
        return
    depth = getattr(_tls, "jit", 0)
    if depth > 1:
        _tls.jit = depth - 1
        return
    # depth 0: a phase that began before listen() did; it is outermost too
    _tls.jit = 0
    if depth:
        _tls.span.end()
        _tls.span = None
    name = _program_name(fun_name)
    metrics.histogram(f"jit.{kind}_s").observe(seconds)
    metrics.counter(f"{_PROGRAM}{name}.seconds").inc(seconds)
    if kind == "backend":
        metrics.counter(f"{_PROGRAM}{name}.builds").inc()
    if not getattr(_tls, "phases", 0):
        metrics.counter("jit.unphased_s").inc(seconds)


def _on_event(event: str, **_kw) -> None:
    counted = _CACHE_COUNTS.get(event)
    if counted is None:
        return
    metrics.counter(counted).inc()
    # the cache answers inside the backend phase of the program it serves
    if counted == "jit.cache_hits" and getattr(_tls, "jit", 0):
        metrics.counter(f"{_PROGRAM}{_tls.program}.cache_hits").inc()


def listen() -> None:
    """Subscribe the compile ledger to JAX's monitoring events, once a
    process (JAX offers no reason to do it twice, and a second set of
    listeners would count every program twice). Called where a process
    first has jax: ``utils/compile_cache.enable()`` and ``Zoo.Start``."""
    global _listening
    with _listen_lock:
        if _listening:
            return
        from jax import monitoring
        monitoring.register_scalar_listener(_on_start)
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _listening = True


def report(snapshot=None) -> List[dict]:
    """The programs JAX built in this process, costliest first: one
    ``{"program", "seconds", "builds", "cache_hits"}`` a name, read off the
    ``jit.program.*`` counters of ``snapshot`` (this process's registry
    where none is given). ``seconds`` sums tracing, lowering and the
    backend; ``builds`` counts the times the backend was asked, of which
    ``cache_hits`` were loads from the persistent cache."""
    rows = {}
    for key, rec in (metrics.snapshot() if snapshot is None
                     else snapshot).items():
        if not key.startswith(_PROGRAM):
            continue
        name, _, field = key[len(_PROGRAM):].rpartition(".")
        row = rows.setdefault(name, {"program": name, "seconds": 0.0,
                                     "builds": 0, "cache_hits": 0})
        row[field] = (float(rec["value"]) if field == "seconds"
                      else int(rec["value"]))
    return sorted(rows.values(), key=lambda r: (-r["seconds"], r["program"]))
