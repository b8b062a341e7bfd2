#!/usr/bin/env python3
"""Cuts a neutral trace (``run.py --keep-trace``) down to a stretch of a
few hundred device operations, small enough to keep under ``tests/data/``,
and writes beside it what the reducers make of it today.

    python3 benchmark/tools/trim_trace.py <cell>.trace.json <out-prefix> [ops]

-> ``<out-prefix>.trace.json`` and ``<out-prefix>.expected.json``. The
expected numbers are a record of the arithmetic at the time of recording:
``tests/test_reducers.py`` holds every later version of the reducers to
them.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv) -> int:
    from benchmark.harness import trace
    from benchmark.harness.run_record import Run
    from benchmark.layer_metrics import (collective_busy_pct,
                                         custom_call_busy_pct,
                                         device_idle_pct)
    src, prefix = argv[0], argv[1]
    keep = int(argv[2]) if len(argv) > 2 else 300
    with open(src) as f:
        tr = json.load(f)
    lo, hi = tr["window"]
    # a stretch from the middle of the window, the same on every chip
    ops0 = [op for op in tr["devices"][0]["ops"] if lo <= op[1] <= hi]
    first = ops0[len(ops0) // 2]
    last = ops0[min(len(ops0) // 2 + keep, len(ops0) - 1)]
    a, b = first[1], last[1] + last[2]
    cut = {"devices": [{**d, "ops": [op for op in d["ops"]
                                     if op[1] >= a and op[1] + op[2] <= b]}
                       for d in tr["devices"]],
           "host": [e for e in tr["host"]
                    if e[1] < b and e[1] + e[2] > a
                    and e[0] != trace.WINDOW_SPAN],
           "window": [a, b], "stat_keys": tr.get("stat_keys", [])}
    run = Run(cell=None, seed=0, seconds=0.0, traced=True, rehearsal=False,
              trace=cut)
    expected = {"device_idle_pct": device_idle_pct.read(run),
                "custom_call_busy_pct": custom_call_busy_pct.read(run),
                "collective_busy_pct": collective_busy_pct.read(run),
                "top_op": trace.breakdown(
                    cut, run.trace_summary())["device_ops"][0][0]}
    with open(prefix + ".trace.json", "w") as f:
        json.dump(cut, f, separators=(",", ":"))
    with open(prefix + ".expected.json", "w") as f:
        json.dump(expected, f, indent=1)
    print(f"kept {sum(len(d['ops']) for d in cut['devices'])} operations on "
          f"{len(cut['devices'])} chip(s) and {len(cut['host'])} host spans "
          f"over {(b - a) / 1e6:.3f} ms: {expected}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
