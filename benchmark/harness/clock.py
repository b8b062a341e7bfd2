"""The run's own clocks: seconds since the process started, and the
percentile the latency metrics use."""

from __future__ import annotations

import math
import os
import time

_IMPORTED = time.perf_counter()


def _age_at_import() -> float:
    """Seconds this process had lived when this module was imported, from
    the kernel's own record (interpreter start-up counts as set-up); 0 where
    /proc cannot say."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


_AGE = _age_at_import()


def since_process_start() -> float:
    return _AGE + time.perf_counter() - _IMPORTED


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (no interpolation: the value is one that was
    measured)."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of no samples")
    return float(ordered[max(1, math.ceil(len(ordered) * q / 100)) - 1])
