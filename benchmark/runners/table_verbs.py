"""Drives one MatrixTable through the host-plane verbs from worker
threads: the reference's ``Test/test_matrix_perf.cpp`` traffic.

Each of the mix's workers runs under ``MV_WorkerContext`` in a closed
loop: a blocking ``AddRows`` on a set of distinct rows, then a blocking
``GetRows`` of the same rows. Id sets and deltas come from a seeded pool
made before the window; deltas are small whole numbers, so what the table
must hold afterwards does not depend on the order the engine applied them
in and is checked bit for bit.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark.harness import clock, trace, traffic
from benchmark.harness.run_record import Stopwatch
from benchmark.reference import table_replay


def _quantiles(ms) -> str:
    if not ms:
        return "none"
    return " / ".join(f"{clock.percentile(ms, q):.2f}" for q in (50, 95, 99))


class Runner:
    def __init__(self, cell, seed: int, rehearsal: bool):
        self.cell, self.seed = cell, seed
        self.cfg, self.mix = cell.config, cell.traffic
        self.up = False

    # -- set-up -------------------------------------------------------------

    def setup(self, workdir: str) -> None:
        import multiverso_tpu as mv
        from multiverso_tpu.tables import MatrixTableOption
        cfg, mix = self.cfg, self.mix
        self.workers = int(mix["workers"])
        mv.MV_Init([f"-num_workers={self.workers}"]
                   + list(cfg.get("world_flags", [])))
        self.up = True
        self.mv = mv
        self.rows, self.cols = int(cfg["rows"]), int(cfg["cols"])
        self.table = mv.MV_CreateTable(MatrixTableOption(
            num_rows=self.rows, num_cols=self.cols))
        rng = np.random.default_rng(self.seed)
        k, sets = int(mix["rows_per_verb"]), int(mix["id_sets_per_worker"])
        self.ids = [traffic.id_pool(rng, self.rows, k, mix["id_law"], sets)
                    for _ in range(self.workers)]
        self.deltas = [[traffic.whole_number_deltas(rng, (k, self.cols))
                        for _ in range(int(mix["deltas_per_worker"]))]
                       for _ in range(self.workers)]
        #: times worker w has added its id set j (a worker writes only its
        #: own row of this)
        self.applied = np.zeros((self.workers, sets), np.int64)
        self._warm_shapes()
        self._drive(rounds=int(mix["warmup_rounds"]))

    def _delta(self, w: int, j: int) -> np.ndarray:
        return self.deltas[w][j % len(self.deltas[w])]

    def _warm_shapes(self) -> None:
        """The engine merges the Adds that queue up in one window into one
        dispatch, whose shape depends on how many arrived together. Which
        windows form races the worker threads, so the threaded warm-up may
        miss a count: every one is compiled here, a lone Add and Get
        through the verbs, the merges of 2 up to the number of workers
        through the server with zero deltas (after ``bench.py``'s
        ``_warm_merged_shapes``)."""
        srv = self.table.server()
        zeros = np.zeros_like(self.deltas[0][0])
        with self.mv.MV_WorkerContext(0):
            self.table.AddRows(self.ids[0][0], zeros)
            self.table.GetRows(self.ids[0][0])
        for n in range(2, self.workers + 1):
            srv.ProcessAddRun([{"row_ids": self.ids[w][0], "values": zeros,
                                "option": None} for w in range(n)])

    # -- the closed loop ----------------------------------------------------

    def _drive(self, seconds: float = 0.0, rounds: int = 0) -> dict:
        """Every worker loops Add-then-Get until ``seconds`` have passed
        (or for ``rounds`` rounds). -> the window's record."""
        gate = threading.Barrier(self.workers + 1)
        lat_ms = [[] for _ in range(self.workers)]
        failed = [0] * self.workers
        deadline = [0.0]

        def work(w: int) -> None:
            sets = len(self.ids[w])
            with self.mv.MV_WorkerContext(w):
                gate.wait()
                done = 0
                while (done < rounds if rounds
                       else time.perf_counter() < deadline[0]):
                    j = done % sets
                    ids, delta = self.ids[w][j], self._delta(w, j)
                    try:
                        with trace.span("bench.verb"):
                            t0 = time.perf_counter()
                            self.table.AddRows(ids, delta)
                            t1 = time.perf_counter()
                        self.applied[w, j] += 1
                        with trace.span("bench.verb"):
                            got = self.table.GetRows(ids)
                            t2 = time.perf_counter()
                        if got.shape != delta.shape:
                            failed[w] += 1
                        lat_ms[w] += [(t1 - t0) * 1e3, (t2 - t1) * 1e3]
                    except Exception as exc:   # counted, and told once
                        if not failed[w]:
                            print(f"worker {w}: verb failed: {exc!r}",
                                  flush=True)
                        failed[w] += 2
                    done += 1

        threads = [threading.Thread(target=work, args=(w,), daemon=True)
                   for w in range(self.workers)]
        for t in threads:
            t.start()
        with Stopwatch() as sw:
            deadline[0] = time.perf_counter() + seconds
            gate.wait()
            for t in threads:
                t.join()
        ops = [x for per in lat_ms for x in per]
        k = int(self.mix["rows_per_verb"])
        return {"wall_s": sw.wall_s, "cpu_s": sw.cpu_s, "op_ms": ops,
                "rows": k * len(ops), "adds": len(ops) // 2,
                "attempted": len(ops) + sum(failed), "failed": sum(failed),
                "notes": [f"{len(ops)} blocking verbs of {k} rows from "
                          f"{self.workers} workers, closed loop; ms at "
                          "p50 / p95 / p99: AddRows "
                          + _quantiles(ops[0::2]) + ", GetRows "
                          + _quantiles(ops[1::2])]}

    def window(self, seconds: float, traced: bool) -> dict:
        return self._drive(seconds=float(self.mix["traced_seconds"])
                           if traced else seconds)

    # -- correctness --------------------------------------------------------

    def check(self) -> dict:
        rng = np.random.default_rng(self.seed + 1)
        n = int(self.cell.workload["sample_rows"])
        touched = np.unique(np.concatenate(
            [ids for per in self.ids for ids in per]))
        sample = np.sort(rng.choice(touched, min(n, len(touched)),
                                    replace=False)).astype(np.int32)
        adds = [(self.ids[w][j], self._delta(w, j), int(self.applied[w, j]))
                for w in range(self.workers)
                for j in range(len(self.ids[w]))]
        want = table_replay.expected_rows(sample, self.cols, adds)
        got = self.table.GetRows(sample)
        exact = np.array_equal(got, want)
        notes = [("ok: " if exact else "FAILED: ")
                 + f"{len(sample)} sampled rows equal a numpy replay of the "
                 f"{int(self.applied.sum())} Adds, bit for bit"]
        free = np.setdiff1d(
            rng.integers(0, self.rows, 4 * n).astype(np.int32), touched)[:n]
        clean = not np.any(self.table.GetRows(free))
        notes.append(("ok: " if clean else "FAILED: ")
                     + f"{len(free)} sampled rows that no Add named are "
                     "still zero")
        return {"correct": exact and clean, "notes": notes}

    def close(self) -> None:
        if self.up:
            self.mv.MV_ShutDown()
            self.up = False
