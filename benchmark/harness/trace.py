"""From the profiler's trace to numbers: the one place that reads xplane.

``record()`` wraps a stretch of the run in a JAX profiler trace with the
benchmark's own host spans (``jax.profiler.TraceAnnotation``) in it.
``parse_xplane()`` turns the ``.xplane.pb`` into a small neutral form
(plain lists, JSON-serialisable) that every reducer below and every reader
under ``layer_metrics/`` works on; ``tests/data/`` keeps such a form
recorded on the chip.

Neutral form::

    {"devices": [{"name": "/device:TPU:0", "line": "XLA Ops",
                  "ops": [[name, start_ns, dur_ns, category], ...]}, ...],
     "host": [[name, start_ns, dur_ns, thread], ...],
     "window": [start_ns, end_ns],
     "stat_keys": [the stats the trace attaches to a device operation]}

Device operations may nest (a ``while`` holds its body's operations), so
busy time is the union of intervals, and an operation's own time is its
duration less its children's.
"""

from __future__ import annotations

import contextlib
import glob
import os
import shutil

#: host spans worth keeping: the benchmark's and the program's own
HOST_SPAN_PREFIXES = ("bench.", "worker.", "server.", "actor.")
WINDOW_SPAN = "bench.window"
#: device-plane lines that hold single operations, best first
_OP_LINES = ("XLA Ops",)
#: device-plane lines that hold something else (whole programs, steps)
_NOT_OP_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                 "Framework Name Scope", "Source code")

_COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
                "reduce-scatter", "all-to-all", "collective-broadcast",
                "ragged-all-to-all")


def short_name(text: str) -> str:
    """The TPU's trace prints an operation as its whole HLO line,
    ``%name = shape opcode(operands), attributes``; the name before the
    ``=`` is what stays the same from run to run and fits a report."""
    return text.split(" = ", 1)[0].lstrip("%")


def category(text: str, stats: dict) -> str:
    """``collective``, ``custom-call`` or whatever category the trace gives
    the operation (``other`` where it gives none), from the opcode in the
    operation's HLO line, its name, or its ``hlo_category`` stat."""
    name = short_name(text).lower()
    body = text.split(" = ", 1)[1] if " = " in text else ""
    if name.startswith(_COLLECTIVES) or any(
            f" {c}(" in body or f" {c}-start(" in body or f" {c}-done(" in
            body for c in _COLLECTIVES):
        return "collective"
    given = str(stats.get("hlo_category", "")).lower()
    if (" custom-call(" in body or "custom" in given
            or "custom-call" in name or "custom_call" in name):
        return "custom-call"
    if any(c in given for c in _COLLECTIVES):
        return "collective"
    return given or "other"


def span(name: str):
    """A host span in the profiler's trace; costs nothing measurable when
    no trace runs."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def record(logdir: str, cpu_stand_in: bool = False):
    """Trace the ``with`` block. Yields a dict that holds, once the block
    has ended, the neutral form under ``trace`` (nothing if the profiler
    wrote no file). The Python tracer is off: it slows the host it is
    meant to watch and the annotations say what is needed."""
    import jax
    result: dict = {}
    shutil.rmtree(logdir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        with span(WINDOW_SPAN):
            yield result
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if files:
        result["trace"] = parse_xplane(max(files, key=os.path.getmtime),
                                       cpu_stand_in)


def _op_lines(plane):
    lines = list(plane.lines)
    named = [ln for ln in lines if ln.name in _OP_LINES]
    return named or [ln for ln in lines if ln.name not in _NOT_OP_LINES]


def parse_xplane(path: str, cpu_stand_in: bool = False) -> dict:
    """``cpu_stand_in`` is for rehearsals only: the CPU backend has no
    device plane, so its XLA worker threads stand in for one and the
    reducers have something to reduce."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, host, stat_keys = [], [], set()
    for plane in data.planes:
        if cpu_stand_in and plane.name.startswith("/host:CPU"):
            ops = [[short_name(ev.name), int(ev.start_ns),
                    int(ev.duration_ns), category(ev.name, {})]
                   for line in plane.lines if line.name.startswith("tf_XLA")
                   for ev in line.events]
            if ops:
                ops.sort(key=lambda e: (e[1], -e[2]))
                devices.append({"name": "cpu stand-in", "line": "tf_XLA*",
                                "ops": ops})
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops, used = [], []
            for line in _op_lines(plane):
                used.append(line.name)
                categorised = None      # do this line's stats name one?
                for ev in line.events:
                    stats = {}
                    if categorised is not False:
                        stats = {str(k): v for k, v in ev.stats}
                        stat_keys.update(stats)
                        categorised = "hlo_category" in stats
                    ops.append([short_name(ev.name), int(ev.start_ns),
                                int(ev.duration_ns),
                                category(ev.name, stats)])
            if ops:
                ops.sort(key=lambda e: (e[1], -e[2]))
                devices.append({"name": plane.name, "line": "+".join(used),
                                "ops": ops})
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIXES):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns), line.name])
    host.sort(key=lambda e: e[1])
    window = next(([e[1], e[1] + e[2]] for e in host
                   if e[0] == WINDOW_SPAN), None)
    if window is None:
        starts = [op[1] for d in devices for op in d["ops"]]
        ends = [op[1] + op[2] for d in devices for op in d["ops"]]
        window = [min(starts), max(ends)] if starts else [0, 0]
    return {"devices": devices, "host": host, "window": window,
            "stat_keys": sorted(stat_keys)}


# -- reductions -------------------------------------------------------------

def _clipped(ops, lo: int, hi: int):
    for name, start, dur, cat in ops:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, a, b, cat


def busy_intervals(ops, lo: int, hi: int) -> list:
    """The union of the operations' intervals inside [lo, hi], as sorted
    disjoint [a, b] pairs."""
    merged: list = []
    for _, a, b, _ in sorted(_clipped(ops, lo, hi), key=lambda e: e[1]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_ns(ops, lo: int, hi: int) -> int:
    return sum(b - a for a, b in busy_intervals(ops, lo, hi))


def self_times(ops, lo: int, hi: int) -> list:
    """[(name, category, own_ns)] for each operation inside [lo, hi]: its
    duration less the time its nested operations cover."""
    out, stack = [], []     # stack of [name, cat, end, own_ns]

    def close(until: int) -> None:
        while stack and stack[-1][2] <= until:
            name, cat, _, own = stack.pop()
            out.append((name, cat, own))

    for name, a, b, cat in sorted(_clipped(ops, lo, hi),
                                  key=lambda e: (e[1], -e[2])):
        close(a)
        if stack:
            stack[-1][3] -= min(b, stack[-1][2]) - a
        stack.append([name, cat, b, b - a])
    close(hi + 1)
    return out


def summary(trace: dict) -> dict:
    """Per device: busy seconds, own seconds by operation name and by
    category, all inside the traced window."""
    lo, hi = trace["window"]
    per_device = []
    for dev in trace["devices"]:
        by_name: dict = {}
        by_cat: dict = {}
        for name, cat, own in self_times(dev["ops"], lo, hi):
            by_name[name] = by_name.get(name, 0) + own
            by_cat[cat] = by_cat.get(cat, 0) + own
        per_device.append({"name": dev["name"],
                           "busy_s": busy_ns(dev["ops"], lo, hi) / 1e9,
                           "by_name_s": {k: v / 1e9
                                         for k, v in by_name.items()},
                           "by_category_s": {k: v / 1e9
                                             for k, v in by_cat.items()}})
    return {"window_s": (hi - lo) / 1e9, "devices": per_device}


def idle_by_span(trace: dict, device: int = 0) -> dict:
    """Idle seconds of one device inside the window, by the innermost
    (shortest) recorded host span covering the middle of each gap."""
    import numpy as np
    lo, hi = trace["window"]
    if not trace["devices"]:
        return {}
    names = [e[0] for e in trace["host"]]
    starts = np.array([e[1] for e in trace["host"]], np.int64)
    durs = np.array([e[2] for e in trace["host"]], np.int64)
    out: dict = {}
    at = lo
    for a, b in busy_intervals(trace["devices"][device]["ops"], lo, hi) + [
            [hi, hi]]:
        if a > at:
            mid = (at + a) // 2
            covering = np.flatnonzero((starts <= mid) & (starts + durs >= mid))
            name = (names[covering[np.argmin(durs[covering])]]
                    if len(covering) else "(no span)")
            out[name] = out.get(name, 0.0) + (a - at) / 1e9
        at = max(at, b)
    return out


def own_time_by_name(s: dict) -> dict:
    """Own seconds by operation name, summed over the devices of a
    ``summary()``."""
    ops: dict = {}
    for dev in s["devices"]:
        for name, secs in dev["by_name_s"].items():
            ops[name] = ops.get(name, 0.0) + secs
    return ops


def breakdown(trace: dict, s: dict, top: int = 10) -> dict:
    """The contract's ``breakdown`` from a trace and its ``summary()``: the
    device operations that took most of their own time (summed over
    devices, under the names the trace prints) and the idle time of the
    busiest device by host span."""
    ops = own_time_by_name(s)
    busiest = max(range(len(s["devices"])),
                  key=lambda i: s["devices"][i]["busy_s"], default=0)
    gaps = idle_by_span(trace, busiest)
    rank = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(ops), "idle_gaps": rank(gaps)}
