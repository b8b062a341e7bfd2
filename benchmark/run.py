#!/usr/bin/env python3
"""One run of one cell of the benchmark: load, warm up, measure, check.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and ``breakdown``
when traced); with ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a short traced
stretch. It exits non-zero and prints no result on anything but a TPU with
exactly the cell's number of chips. ``--rehearsal`` runs the same control
flow tiny on virtual CPU devices; its line says platform ``cpu`` and
``"rehearsal": true`` and is never a result.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from benchmark.harness import clock  # noqa: E402  (first: it dates the process)


def _say(msg: str) -> None:
    print(msg, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on virtual CPU devices; never a result")
    ap.add_argument("--keep-trace", metavar="DIR", default="",
                    help="write the neutral form of the trace here")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(REPO, "multiverso_tpu")):
        _say(f"FAIL: no multiverso_tpu package beside {HERE}: the benchmark "
             "measures the program and does not run without it")
        return 2
    from benchmark.harness import cells, compiles, device, program, trace
    from benchmark.harness.run_record import Run
    cell = cells.load_cell(args.workload).sized(args.rehearsal)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
            f"device_count={cell.chips}").strip()

    cache_dir = program.enable_compile_cache()
    compiled = compiles.CompileCounter()
    try:
        devices = device.require(cell.chips, args.rehearsal)
    except device.WrongDevice as exc:
        _say(f"FAIL: {exc}")
        return 1
    if not args.rehearsal:
        device.peaks(devices[0].device_kind)   # an unknown chip is an error
    _say(f"cell {cell.name}: runner {cell.runner}, seed {args.seed}, "
         f"{args.seconds:g} s, trace {args.trace}, {len(devices)} x "
         f"{devices[0].device_kind} ({devices[0].platform}), compile cache "
         f"{cache_dir}; the device answered {clock.since_process_start():.3f}"
         " s after the process started")

    run = Run(cell=cell, seed=args.seed, seconds=args.seconds,
              traced=bool(args.trace), rehearsal=args.rehearsal,
              devices=devices)
    runner = cells.load_runner(cell.runner).Runner(cell, args.seed,
                                                   args.rehearsal)
    workdir = tempfile.mkdtemp(prefix="bench_")
    try:
        runner.setup(workdir)
        at_setup = compiled.snapshot()
        run.compiles_setup = compiles.between(
            {"requests": 0, "hits": 0}, at_setup)
        run.setup_s = clock.since_process_start()
        _say(f"set-up {run.setup_s:.3f} s: {run.compiles_setup['requests']} "
             f"programs asked for, {run.compiles_setup['hits']} from the "
             f"cache, {run.compiles_setup['compiled']} compiled")
        run.counters_before = program.metrics_snapshot()
        if run.traced:
            program.bridge_spans(True)
            try:
                with trace.record(os.path.join(workdir, "trace"),
                                  cpu_stand_in=args.rehearsal) as got:
                    run.window = runner.window(args.seconds, traced=True)
            finally:
                program.bridge_spans(False)
            run.trace = got.get("trace")
            if args.keep_trace and run.trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                with open(os.path.join(args.keep_trace,
                                       f"{cell.name}.trace.json"), "w") as f:
                    json.dump(run.trace, f)
        else:
            run.window = runner.window(args.seconds, traced=False)
        run.compiles_window = compiles.between(at_setup,
                                               compiled.snapshot())
        run.counters_after = program.metrics_snapshot()
        run.memory_peak_bytes = device.memory_peak_bytes(devices)
        w = run.window
        _say(f"window {w['wall_s']:.3f} s wall, {w['cpu_s']:.3f} s cpu, "
             f"{w['attempted']} attempted, {w['failed']} failed; "
             f"{run.compiles_window['requests']} programs asked for inside "
             f"it, {run.compiles_window['compiled']} compiled"
             + (" -- WARNING: the warm-up missed a shape"
                if run.compiles_window["compiled"] else ""))
        for note in w.get("notes", []):
            _say(f"  {note}")
        _say(f"  device memory: peak {run.memory_peak_bytes} bytes on the "
             f"fullest chip, of {device.memory_limit_bytes(devices)}")
        verdict = runner.check()
        for note in verdict["notes"]:
            _say(f"  check: {note}")
    except Exception:
        traceback.print_exc()
        _say("FAIL: the run raised; no result")
        return 1
    finally:
        try:
            runner.close()
        finally:
            shutil.rmtree(workdir, ignore_errors=True)

    entries, readers = ((cell.per_layer, "layer_metrics") if run.traced
                        else (cell.end_to_end, "end_to_end"))
    metrics = {}
    for entry in entries:
        value = cells.load_reader(readers, entry["name"])(run)
        if value is not None:
            metrics[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}
    dev = device.describe(devices, run.memory_peak_bytes)
    line = {"correct": bool(verdict["correct"]),
            "attempted": int(run.window["attempted"]),
            "failed": int(run.window["failed"]),
            "metrics": metrics, "device": dev}
    s = run.trace_summary() if run.traced else None
    if run.traced and s is None and not args.rehearsal:
        _say("FAIL: the traced window holds no device operation")
        return 1
    if s is not None:
        busy = [d["busy_s"] for d in s["devices"]]
        _say("device busy s per chip: " + ", ".join(f"{b:.4f}" for b in busy)
             + f" of {s['window_s']:.4f} s traced (largest idle share "
             f"{100 * (1 - min(busy) / s['window_s']):.2f} %)")
        _say("trace: operations from line(s) "
             + ", ".join(sorted({d["line"] for d in run.trace["devices"]}))
             + "; stats on an operation: "
             + ", ".join(run.trace.get("stat_keys", [])[:24]))
        dev["busy_s"] = sum(busy) / len(busy)
        dev["window_s"] = s["window_s"]
        line["breakdown"] = trace.breakdown(run.trace, s)
    if args.rehearsal:
        line["rehearsal"] = True
    _say(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
