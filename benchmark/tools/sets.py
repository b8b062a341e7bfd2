#!/usr/bin/env python3
"""Two sets of runs of one cell, as the driver measures a new cell: each run
a new process with the next seed, one after another, then for every metric
each set's median and spread (the distance between the quartiles over the
median) and the second median over the first.

    chiprun --chips <n> -- python3 benchmark/tools/sets.py \
        --workload <cell> --seed <first> --runs 12 --seconds 10 --trace 0

This process never touches JAX, so each child has the chips to itself. Every
run's whole output goes to ``chiprun_out/<tag>/`` under the directory the call
was made from; the last line printed is the last run's result line.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def spread(values) -> float:
    """Distance between the quartiles over the median (quartiles by linear
    interpolation between the ordered values)."""
    q1, _, q3 = statistics.quantiles(values, n=4, method="inclusive")
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1, help="the first run's")
    ap.add_argument("--runs", type=int, default=12, help="both sets together")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tag", default="sets")
    ap.add_argument("--apart", type=int, default=0, metavar="N",
                    help="N more runs first, kept out of the sets: a first "
                    "run in a checkout compiles")
    ap.add_argument("--rehearsal", action="store_true",
                    help="pass --rehearsal on: finds faults, measures nothing")
    args = ap.parse_args(argv)

    # under the directory the call was made from: the chip tool brings back
    # its own chiprun_out/ and not one inside an unpacked checkout
    out_dir = os.path.join(os.getcwd(), "chiprun_out", args.tag)
    os.makedirs(out_dir, exist_ok=True)
    lines = []
    for i in range(-args.apart, args.runs):
        seed = args.seed + args.apart + i
        cmd = [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
               "--workload", args.workload, "--seed", str(seed),
               "--seconds", f"{args.seconds:g}", "--trace", str(args.trace)]
        cmd += ["--rehearsal"] if args.rehearsal else []
        began = time.perf_counter()
        done = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True)
        took = time.perf_counter() - began
        stem = os.path.join(out_dir,
                            f"{args.workload}.s{seed}.t{args.trace}")
        with open(stem + ".out", "w") as f:
            f.write(done.stdout)
        with open(stem + ".err", "w") as f:
            f.write(done.stderr)
        body = done.stdout.strip().splitlines()
        for told in body[:-1]:
            if told.startswith(("cell ", "set-up ", "window ")):
                print("  " + told, flush=True)
        try:
            line = json.loads(body[-1]) if done.returncode == 0 else None
        except (ValueError, IndexError):
            line = None
        if not isinstance(line, dict) or "metrics" not in line:
            print(f"run {i} seed {seed}: exit {done.returncode} after "
                  f"{took:.1f} s, no result line\n" + done.stdout[-2000:]
                  + done.stderr[-2000:], flush=True)
            return 1
        if i >= 0:
            lines.append(line)
        print(f"run {i} seed {seed} at {time.strftime('%H:%M:%S')}: "
              f"{took:.1f} s, correct {line['correct']}, "
              + ", ".join(f"{k} {v['value']:.6g}"
                          for k, v in line["metrics"].items()), flush=True)

    half = len(lines) // 2
    sets = {"A": lines[:half], "B": lines[half:]}
    for name in lines[0]["metrics"]:
        told = []
        medians = {}
        for tag, part in sets.items():
            vals = [r["metrics"][name]["value"] for r in part
                    if name in r["metrics"]]
            if len(vals) < 2:
                continue
            medians[tag] = statistics.median(vals)
            told.append(f"set {tag} n={len(vals)} median {medians[tag]:.6g} "
                        f"spread {100 * spread(vals):.3f} %")
        if len(medians) == 2:
            told.append("B over A "
                        f"{100 * (medians['B'] / medians['A'] - 1):+.3f} %")
        print(f"{args.workload} {name}: " + "; ".join(told), flush=True)
    wrong = sum(not r["correct"] for r in lines)
    print(f"{len(lines)} runs, {wrong} not correct", flush=True)
    print(json.dumps(lines[-1]), flush=True)
    return 0 if not wrong else 1


if __name__ == "__main__":
    sys.exit(main())
