#!/usr/bin/env python3
"""Where one run's set-up went, and which programs JAX built in it: a
run of ``benchmark/run.py`` with the same arguments, and after its result
line the program's start-up ledger (``multiverso_tpu/telemetry/
startup.py``) as the run's two snapshots hold it.

    python3 benchmark/tools/setup_table.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1> [--rehearsal] [--out table.json]

Set-up by phase (the gauges and histograms whose seconds add up in
``startup.phased_s``, and the compile ledger's ``jit.*``), the ten programs
that cost most seconds of tracing, lowering and compiling or loading, and
the programs built inside the window (there should be none). It edits
nothing of the harness: it keeps the snapshots ``run.py`` takes through
``harness.program.metrics_snapshot``. Prints nothing on a program without
the ledger.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

PHASES = ("mv.import_s", "mv.init_s", "mv.init.mesh_s", "mv.init.planes_s",
          "we.prepare.dictionary_s", "we.prepare.tokenizer_s",
          "we.prepare.sampler_s", "we.prepare.huffman_s")
JIT = ("jit.trace_s", "jit.lower_s", "jit.backend_s", "jit.cache_load_s",
       "table.create_s")
COUNTERS = ("startup.phased_s", "jit.unphased_s", "jit.cache_hits",
            "jit.cache_misses")


def table(before: dict, after: dict, setup_s=None) -> dict:
    """The ledger of one run, from the snapshot at set-up's end and the one
    after the window."""
    from multiverso_tpu.telemetry import startup
    out = {"gauges": {n: before[n]["value"] for n in PHASES if n in before},
           "histograms": {n: {"count": before[n]["count"],
                              "sum": before[n]["sum"]}
                          for n in JIT if n in before},
           "counters": {n: before[n]["value"]
                        for n in COUNTERS if n in before},
           "programs": startup.report(before)}
    if setup_s is not None and "startup.phased_s" in before:
        out["setup_s"] = setup_s
        out["unaccounted_s"] = (
            setup_s - before["startup.phased_s"]["value"]
            - before.get("jit.unphased_s", {"value": 0.0})["value"])
    was = {r["program"]: r for r in out["programs"]}
    window = []
    for row in startup.report(after):
        old = was.get(row["program"],
                      {"seconds": 0.0, "builds": 0, "cache_hits": 0})
        if row["seconds"] > old["seconds"]:
            window.append({"program": row["program"], **{
                k: row[k] - old[k]
                for k in ("seconds", "builds", "cache_hits")}})
    out["window_programs"] = sorted(window, key=lambda r: -r["seconds"])
    return out


def show(t: dict) -> None:
    if "setup_s" in t:
        print(f"set-up {t['setup_s']:.3f} s: phases "
              f"{t['counters']['startup.phased_s']:.3f}, compiles outside "
              f"every phase {t['counters'].get('jit.unphased_s', 0.0):.3f}, "
              f"unaccounted {t['unaccounted_s']:.3f}")
    for name, value in t["gauges"].items():
        print(f"  {name:28s} {value:9.3f} s")
    for name, h in t["histograms"].items():
        print(f"  {name:28s} {h['sum']:9.3f} s in {h['count']} samples")
    print("  cache hits {:.0f}, misses {:.0f}".format(
        t["counters"].get("jit.cache_hits", 0),
        t["counters"].get("jit.cache_misses", 0)))
    print(f"  {len(t['programs'])} programs by name; the ten that cost "
          "most (seconds of trace + lower + backend, builds, cache hits):")
    for r in t["programs"][:10]:
        print(f"    {r['program']:40s} {r['seconds']:8.3f} {r['builds']:4d} "
              f"{r['cache_hits']:4d}")
    print(f"  built inside the window: {len(t['window_programs'])}")
    for r in t["window_programs"][:10]:
        print(f"    {r['program']:40s} {r['seconds']:8.3f} {r['builds']:4d} "
              f"{r['cache_hits']:4d}")


def main(argv) -> int:
    out = ""
    if "--out" in argv:
        at = argv.index("--out")
        out, argv = argv[at + 1], argv[:at] + argv[at + 2:]
    from benchmark import run as bench_run
    from benchmark.harness import clock, program
    kept, take = [], program.metrics_snapshot

    def keeping():
        kept.append((clock.since_process_start(), take()))
        return kept[-1][1]

    program.metrics_snapshot = keeping
    code = bench_run.main(argv)
    if len(kept) == 2 and "startup.phased_s" in kept[0][1]:
        # run.py reads setup_s one line before it takes the first snapshot
        t = table(kept[0][1], kept[1][1], setup_s=kept[0][0])
        show(t)
        if out:
            os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
            with open(out, "w") as f:
                json.dump(t, f)
    return code


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
