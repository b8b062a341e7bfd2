#!/usr/bin/env python3
"""What the host spans of a kept trace (``run.py --keep-trace``) add up
to: for each span name its count, seconds and own seconds inside the
window, and the idle seconds of the busiest chip that fall to it.

    python3 benchmark/tools/span_table.py <cell>.trace.json [expected.json]

With a second path it also writes what every span reader under
``layer_metrics/`` makes of the trace today, for ``tests/data/``:
``tests/test_spans.py`` holds later versions of the arithmetic to it.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

SPAN_READERS = ("loader_wait_pct", "block_host_ms",
                "window_finalize_ms_mean", "window_merge_ms_mean",
                "window_dispatch_ms_mean", "round_prepare_pct",
                "round_dispatch_pct")


def read_all(tr: dict) -> dict:
    """{metric: value} of the span readers that find something to read."""
    from benchmark.harness import cells
    from benchmark.harness.run_record import Run
    run = Run(cell=None, seed=0, seconds=0.0, traced=True, rehearsal=False,
              trace=tr)
    got = {name: cells.load_reader("layer_metrics", name)(run)
           for name in SPAN_READERS}
    return {k: v for k, v in got.items() if v is not None}


def main(argv) -> int:
    from benchmark.harness import spans, trace
    with open(argv[0]) as f:
        tr = json.load(f)
    wall = spans.window_s(tr)
    gaps = {}
    if tr["devices"]:
        s = trace.summary(tr)
        busiest = max(range(len(s["devices"])),
                      key=lambda i: s["devices"][i]["busy_s"])
        gaps = trace.idle_by_span(tr, busiest)
    names = sorted({e[0] for e in tr["host"]},
                   key=lambda n: -(spans.total_s(tr, n) or 0.0))
    print(f"window {wall:.4f} s, {len(tr['host'])} host spans kept "
          f"({len(tr['host']) / wall:.0f} a second), idle of the busiest "
          f"chip {sum(gaps.values()):.4f} s")
    print(f"{'span':44s} {'count':>7s} {'total s':>9s} {'own s':>9s} "
          f"{'idle s':>9s}")
    for n in names:
        print(f"{n:44s} {spans.count(tr, n):7d} {spans.total_s(tr, n):9.4f} "
              f"{spans.self_s(tr, n):9.4f} {gaps.get(n, 0.0):9.4f}")
    if "(no span)" in gaps:
        print(f"{'(no span)':44s} {'':7s} {'':9s} {'':9s} "
              f"{gaps['(no span)']:9.4f}")
    metrics = read_all(tr)
    print(json.dumps(metrics))
    if len(argv) > 1:
        with open(argv[1], "w") as f:
            json.dump(metrics, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
