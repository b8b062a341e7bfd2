"""What one run hands to the metric readers."""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional


class Stopwatch:
    """Wall and CPU seconds of a ``with`` block (CPU over all threads of
    the process)."""

    def __enter__(self):
        self._w, self._c = time.perf_counter(), time.process_time()
        return self

    def __exit__(self, *exc):
        self.wall_s = time.perf_counter() - self._w
        self.cpu_s = time.process_time() - self._c
        return False


@dataclass
class Run:
    cell: object
    seed: int
    seconds: float
    traced: bool
    rehearsal: bool
    devices: list = field(default_factory=list)
    setup_s: float = 0.0
    compiles_setup: dict = field(default_factory=dict)
    compiles_window: dict = field(default_factory=dict)
    #: the runner's record of the window: wall_s, cpu_s, attempted, failed
    #: and whatever it counted (items, rows, op_ms, adds, ...)
    window: dict = field(default_factory=dict)
    counters_before: dict = field(default_factory=dict)
    counters_after: dict = field(default_factory=dict)
    trace: Optional[dict] = None
    memory_peak_bytes: int = 0
    _summary: Optional[dict] = None

    def trace_summary(self) -> Optional[dict]:
        """harness.trace.summary() of the traced window, computed once."""
        if self.trace is None or not self.trace["devices"]:
            return None
        if self._summary is None:
            from benchmark.harness import trace
            self._summary = trace.summary(self.trace)
        return self._summary
