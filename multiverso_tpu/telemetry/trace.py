"""Span-based structured tracing across the actor runtime.

Dapper-style: a *span* is a named, timed region on one thread; spans
nest through a thread-local stack, and a span's context ``(trace_id,
span_id)`` rides on ``Message.trace_ctx`` so the tree continues on the
thread that dequeues the message — one tree follows a verb from the
worker's ``GetAsync/AddAsync`` through the engine mailbox into the
server's window lifecycle (sync/server.py).

Export is Chrome trace-event JSON (`MV_DumpTrace`), loadable in
Perfetto / chrome://tracing:

* complete events (``ph: "X"``) — one per finished span, with
  ``trace_id/span_id/parent_id`` in ``args`` (the tree is explicit even
  across threads);
* flow events (``ph: "s"`` at message enqueue, ``ph: "f"`` at dequeue)
  — Perfetto draws the worker->server mailbox hop as an arrow.

Device correlation: when ``MV_StartProfiler`` has an xplane trace
active (api.py flips :func:`set_xplane`), every span also enters a
``jax.profiler.TraceAnnotation`` of the same name, so host spans appear
on the device timeline next to the XLA ops they dispatched.

Gated by ``-trace`` (default off). The ring buffer is bounded
(:data:`MAX_EVENTS`): a forgotten long-running trace degrades to
keeping the most recent events instead of eating the heap.
"""

from __future__ import annotations

import collections
import functools
import itertools
import json
import os
import threading
import time
from typing import NamedTuple, Optional

from multiverso_tpu.utils.configure import MV_DEFINE_bool, cached_bool_flag
from multiverso_tpu.utils.log import Log

MV_DEFINE_bool("trace", False,
               "span tracing on/off (export with MV_DumpTrace)")

#: the -trace gate, CACHED behind a flag listener (hot-path span entry
#: must not pay a registry-lock GetFlag per message)
enabled = cached_bool_flag("trace", False)

#: completed-event ring bound — oldest events drop first
MAX_EVENTS = 200_000

#: the ring: finished spans (``_Span`` objects, made into events at
#: export) and flow events (dicts). ``deque.append`` is atomic under the
#: interpreter lock, so a span's end takes no lock of ours
_events = collections.deque(maxlen=MAX_EVENTS)
_tls = threading.local()
#: ``next()`` of an ``itertools.count`` is atomic under the interpreter lock
_id_counter = itertools.count(1)
_pid = os.getpid()
#: set by api.MV_StartProfiler/MV_StopProfiler: ``jax.profiler.
#: TraceAnnotation`` while an xplane trace runs (spans bridge into it),
#: else None. Resolved once here, not in every span's ``__enter__``
_annotation = None
#: what :func:`child` names a span that no open span encloses
ORPHAN = "server.table.device"


class SpanContext(NamedTuple):
    trace_id: int
    span_id: int


def _after_fork() -> None:
    global _pid
    _pid = os.getpid()


os.register_at_fork(after_in_child=_after_fork)


def set_xplane(active: bool) -> None:
    global _annotation
    if active:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    else:
        _annotation = None


def _now_us() -> float:
    return time.perf_counter() * 1e6


@functools.lru_cache(maxsize=None)
def _prctl():
    """libc's ``prctl``, or None where there is none to be had."""
    import ctypes
    try:
        fn = ctypes.CDLL(None).prctl
    except (OSError, AttributeError):
        return None
    fn.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_ulong,
                   ctypes.c_ulong, ctypes.c_ulong]
    fn.restype = ctypes.c_int
    return fn


def name_native_thread() -> None:
    """Give the calling thread's Python name to the kernel (``prctl
    (PR_SET_NAME)``, 15 bytes): the profiler names a host line after the
    native thread name, and every thread of an unnamed process reads
    ``python3`` there. A longer name keeps its first 8 and its last 7
    bytes, so that ``mv-server_shard3`` keeps its number. Called first
    thing on the threads the package starts; does nothing where there is
    no ``prctl``."""
    prctl = _prctl()
    if prctl is not None:
        name = threading.current_thread().name.encode()
        if len(name) > 15:
            name = name[:8] + name[-7:]
        prctl(15, name, 0, 0, 0)        # PR_SET_NAME; the kernel copies it


def current_ctx() -> Optional[SpanContext]:
    """The calling thread's innermost open span, or None (used to stamp
    ``Message.trace_ctx`` at enqueue)."""
    top = getattr(_tls, "top", None)
    return top._ctx if top is not None else None


class _NullSpan:
    """Shared no-op context manager: the tracing-off fast path must not
    allocate per call (span() sits on per-message hot paths)."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False

    def end(self) -> None:
        """A held span (:func:`begin`) that tracing-off never opened."""


NULL_SPAN = _NULL_SPAN = _NullSpan()


class _Span:
    """One span, and after its end its own record in the ring: what a
    span pays while a trace runs is two clock reads, an id, a tuple and a
    ``deque.append``; the event's dicts are built at export."""

    __slots__ = ("name", "cat", "args", "_parent", "_prev", "_ctx",
                 "_ann", "_t0", "_dur", "_tid")

    def __init__(self, name, parent, cat, args):
        self.name = name
        self.cat = cat
        self.args = args
        self._parent = parent

    def __enter__(self):
        prev = self._prev = getattr(_tls, "top", None)
        parent = self._parent
        if parent is None and prev is not None:
            parent = self._parent = prev._ctx
        # pid-prefixed so ids from different ranks' dumps never collide
        sid = (_pid << 24) | (next(_id_counter) & 0xFFFFFF)
        self._ctx = SpanContext(parent.trace_id if parent else sid, sid)
        _tls.top = self
        ann = _annotation
        if ann is not None:
            ann = ann(self.name)
            ann.__enter__()
        self._ann = ann
        self._t0 = _now_us()
        return self._ctx

    def __exit__(self, *exc):
        self._dur = _now_us() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        _tls.top = self._prev
        self._prev = None       # the ring keeps this span, not its elders
        self._tid = threading.get_ident()
        _events.append(self)
        return False

    def _event(self) -> dict:
        ev_args = {"trace_id": self._ctx.trace_id,
                   "span_id": self._ctx.span_id,
                   "parent_id": self._parent.span_id if self._parent else 0}
        if self.args:
            ev_args.update(self.args)
        return {"name": self.name, "cat": self.cat, "ph": "X",
                "ts": self._t0, "dur": self._dur, "pid": _pid,
                "tid": self._tid, "args": ev_args}


class _HeldSpan(_Span):
    """A span whose two ends are not one ``with`` block: it begins in
    :func:`begin` and ends at its ``end()``, on the same thread, whatever
    opened and closed there in between. It is no part of the thread's
    nesting: no span becomes its child and it has no parent. The
    profiler's annotation records a start and an end of its own, so held
    spans may overlap each other and end in any order."""

    __slots__ = ()

    def begin(self):
        sid = (_pid << 24) | (next(_id_counter) & 0xFFFFFF)
        self._ctx = SpanContext(sid, sid)
        self._prev = None
        ann = _annotation
        if ann is not None:
            ann = ann(self.name)
            ann.__enter__()
        self._ann = ann
        self._t0 = _now_us()
        return self

    def end(self) -> None:
        self._dur = _now_us() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        self._tid = threading.get_ident()
        _events.append(self)


def span(name: str, parent: Optional[SpanContext] = None, cat: str = "mv",
         args: Optional[dict] = None):
    """Context manager opening a span for the ``with`` block. ``parent``
    overrides the thread-local nesting (pass a message's ``trace_ctx``
    when picking work up from a mailbox). ``with`` yields the span's
    context (None when tracing is off)."""
    if not enabled():
        return _NULL_SPAN
    return _Span(name, parent, cat, args)


def _child_of(cls, suffix: str, args: Optional[dict]):
    top = getattr(_tls, "top", None)
    if top is None:
        return cls(ORPHAN + suffix, None, "server", args)
    return cls(top.name + suffix, None, top.cat, args)


def child(suffix: str, args: Optional[dict] = None):
    """A span named AFTER the innermost span open on this thread:
    ``<its name><suffix>``, in its category (``ORPHAN`` + suffix where
    none is open). For a helper that many verbs share and that must show
    under each by name: a device crossing under
    ``server.table.get.dispatch`` is ``server.table.get.dispatch.place``.
    Tracing off: one flag read, the shared no-op."""
    if not enabled():
        return _NULL_SPAN
    return _child_of(_Span, suffix, args)


def begin(name: str, cat: str = "mv", args: Optional[dict] = None):
    """Open a span that a later line of this thread ends with ``.end()``
    (a wait in a queue: the entry and the exit are different calls).
    Tracing off: one flag read, the shared no-op."""
    if not enabled():
        return _NULL_SPAN
    return _HeldSpan(name, None, cat, args).begin()


def begin_child(suffix: str, args: Optional[dict] = None):
    """:func:`begin`, named as :func:`child` names: a held span
    ``<innermost open span><suffix>`` (a compile that JAX reports by a
    start event and an end event, under the verb that set it off)."""
    if not enabled():
        return _NULL_SPAN
    return _child_of(_HeldSpan, suffix, args).begin()


def flow_start(ctx: Optional[SpanContext], name: str = "mv.msg") -> None:
    """Flow-arrow origin (message enqueue). No-op when ``ctx`` is None
    or tracing is off."""
    if ctx is None or not enabled():
        return
    _events.append({"name": name, "cat": "msg", "ph": "s", "id": ctx.span_id,
             "ts": _now_us(), "pid": _pid,
             "tid": threading.get_ident()})


def flow_end(ctx: Optional[SpanContext], name: str = "mv.msg") -> None:
    """Flow-arrow target (message dequeue on the actor thread)."""
    if ctx is None or not enabled():
        return
    _events.append({"name": name, "cat": "msg", "ph": "f", "bp": "e",
             "id": ctx.span_id, "ts": _now_us(), "pid": _pid,
             "tid": threading.get_ident()})


def chrome_trace(events: list, process_names: Optional[dict] = None,
                 thread_names: Optional[dict] = None) -> dict:
    """Wrap prepared trace events as a Chrome trace-event object
    (Perfetto / chrome://tracing loadable) — THE one writer both the
    live span dump below and offline reconstructions
    (telemetry/critpath.py's merged cross-rank timeline) ride, so the
    export schema cannot fork. ``process_names``: {pid: label};
    ``thread_names``: {(pid, tid): label}."""
    meta = []
    for pid, name in sorted((process_names or {}).items()):
        meta.append({"name": "process_name", "ph": "M", "pid": pid,
                     "tid": 0, "args": {"name": name}})
    for (pid, tid), name in sorted((thread_names or {}).items()):
        meta.append({"name": "thread_name", "ph": "M", "pid": pid,
                     "tid": tid, "args": {"name": name}})
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


def to_chrome_trace() -> dict:
    """The buffered events as a Chrome trace-event object (JSON-ready)."""
    while True:
        try:
            ring = list(_events)
            break
        except RuntimeError:    # a span ended while the ring was copied
            continue
    events = [e if type(e) is dict else e._event() for e in ring]
    out = chrome_trace(events,
                       process_names={_pid: _process_label()})
    # round 22: a (wall, mono) anchor pair sampled at export time. Span
    # timestamps are perf_counter-based (each process its own zero);
    # the fleet trace-merge CLI (telemetry/fleet.py --trace) uses this
    # pair to map every dump onto one wall timeline before refining the
    # residual offset from matched client/server span pairs.
    out["clock"] = {"wall_s": time.time(), "mono_us": _now_us(),
                    "pid": _pid}
    return out


#: process label for dumps/merges — stamped by set_process_label()
#: from contexts that KNOW their identity (MV_Init on trainer ranks,
#: Replica.start on readers). A lazy multihost.process_index() here
#: would put device work on every dump caller's thread (the replica
#: serve loop exports dumps — device-work-domain law).
_PROC_LABEL = "multiverso"


def set_process_label(label: str) -> None:
    global _PROC_LABEL
    _PROC_LABEL = str(label)


def _process_label() -> str:
    return _PROC_LABEL


def dump(path: str) -> str:
    """Write the buffered span tree as Chrome trace JSON to ``path``
    (per-rank file in multihost jobs — each rank holds its own spans)
    and return the path."""
    data = to_chrome_trace()
    with open(path, "w") as f:
        json.dump(data, f)
    Log.Info("telemetry: wrote %d trace events to %s",
             len(data["traceEvents"]), path)
    return path


def clear() -> None:
    _events.clear()


def _reset_for_tests() -> None:
    clear()
    set_xplane(False)
