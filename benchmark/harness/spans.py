"""Arithmetic on the host spans of a trace's neutral form
(``trace["host"]``: ``[name, start_ns, dur_ns, thread]``), shared by the
readers under ``layer_metrics/`` that read the program's spans.

Every figure is taken inside ``trace["window"]``: a span that crosses an
edge of the window counts for the part inside it. A name the trace does
not hold gives ``None``, not 0, and so does a run that was not traced
(``trace`` is None): a program without that span has nothing to read, and
the reader then leaves its metric out.
"""

from __future__ import annotations


def window_s(trace: dict) -> float:
    lo, hi = trace["window"]
    return (hi - lo) / 1e9


def _inside(trace: dict, names) -> list:
    """[start, end] of each span so named, clipped to the window; spans
    wholly outside it are dropped."""
    lo, hi = trace["window"]
    clipped = ([max(start, lo), min(start + dur, hi)]
               for name, start, dur, _ in trace["host"] if name in names)
    return [ab for ab in clipped if ab[1] > ab[0]]


def _named(trace, names) -> bool:
    """Does the trace (None: the run was not traced) hold such a span?"""
    return trace is not None and any(e[0] in names for e in trace["host"])


def count(trace: dict, *names):
    """Spans so named that reach into the window; None if the trace
    holds none at all."""
    if not _named(trace, names):
        return None
    return len(_inside(trace, names))


def total_s(trace: dict, *names):
    """Seconds the spans so named last inside the window, summed (spans
    of several threads add up; one name does not nest in itself here)."""
    if not _named(trace, names):
        return None
    return sum(b - a for a, b in _inside(trace, names)) / 1e9


def mean_ms(trace: dict, name: str):
    """Mean length of a span so named, over those that reach into the
    window."""
    n = count(trace, name)
    if not n:
        return None
    return 1e3 * total_s(trace, name) / n


def per_ms(trace: dict, per: str, *names):
    """Milliseconds of the spans ``names`` for each span ``per`` (for
    each engine window, say); None if either is absent."""
    n, secs = count(trace, per), total_s(trace, *names)
    if not n or secs is None:
        return None
    return 1e3 * secs / n


def share_pct(trace: dict, *names):
    """The spans' seconds over the window's wall, in percent."""
    secs = total_s(trace, *names)
    if secs is None or not window_s(trace):
        return None
    return 100.0 * secs / window_s(trace)


def self_s(trace: dict, name: str):
    """Seconds inside spans ``name`` that no other kept span on the same
    thread covers: the span's own time, by nesting on its thread (a child
    on another thread takes nothing away). A thread is what the neutral
    form names one: ``harness/trace.py`` keeps the trace line's name,
    which on the chip is ``python3`` for every thread of the process, so
    there a span of another thread that falls wholly inside is taken off
    as well (``PERF.md``, Open questions)."""
    if not _named(trace, (name,)):
        return None
    lo, hi = trace["window"]
    own = 0
    for n, start, dur, thread in trace["host"]:
        if n != name or min(start + dur, hi) <= max(start, lo):
            continue
        a, b = max(start, lo), min(start + dur, hi)
        inner = sorted(
            (max(s, a), min(s + d, b)) for c, s, d, t in trace["host"]
            if t == thread and c != name and s >= start
            and s + d <= start + dur)
        covered, at = 0, a
        for c, d in inner:          # the union of the children, clipped
            if d > max(c, at):
                covered += d - max(c, at)
                at = d
        own += (b - a) - covered
    return own / 1e9
