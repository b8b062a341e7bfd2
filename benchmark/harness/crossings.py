"""The table layer's crossings into and out of the device, as the program
records them since PR 35 (``multiverso_tpu/tables/crossing.py``): a leaf
span named after the span it runs in, ``<verb's span>.place`` (a copy
in), ``.call`` (a program's launch), ``.wait`` and ``.take`` (a copy
back), and the counters ``table.device.*``. Shared by the six readers
under ``layer_metrics/`` that read them. A program without them (any
before PR 35) gives ``None`` everywhere, and the reader leaves its metric
out."""

from __future__ import annotations

from benchmark.harness import program, spans


def share_pct(trace, suffix: str):
    """Seconds of every kept span under ``server.`` whose name ends in
    ``suffix``, over the window's wall, in percent (threads add up; the
    caller's ``worker.wait`` is no crossing). None if the trace holds no
    such span or the run was not traced."""
    if trace is None:
        return None
    names = {e[0] for e in trace["host"]
             if e[0].startswith("server.") and e[0].endswith(suffix)}
    if not names:
        return None
    return spans.share_pct(trace, *sorted(names))


def per_op(run, counter: str, scale: float = 1.0):
    """How far the program's counter moved over the window, an operation
    the runner attempted (a step, a round, a verb), times ``scale``."""
    moved = program.counter_delta(run.counters_before, run.counters_after,
                                  counter)
    ops = run.window.get("attempted")
    if moved is None or not ops:
        return None
    return scale * moved / ops
