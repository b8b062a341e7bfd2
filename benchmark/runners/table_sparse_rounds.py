"""Drives one SparseMatrixTable through the host-plane verbs from worker
threads: the sparse arm of the reference's ``Test/test_matrix_perf.cpp``.

Each of the mix's workers runs under ``MV_WorkerContext`` in a closed
loop of rounds, every verb blocking: Get-all (no ids: whatever other
workers changed since my last Get), ``AddRows`` on a set of distinct rows,
Get-all. Id sets and deltas come from a seeded pool made before the
window; deltas are whole numbers, so what the table must hold afterwards
does not depend on the order the engine applied them in and is checked bit
for bit. Which rows a Get returns does depend on that order, so the
freshness protocol is checked from the runner's own record of ids, order
free (``benchmark/reference/sparse_rows.py``).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark.harness import clock, trace, traffic
from benchmark.harness.run_record import Stopwatch
from benchmark.reference import sparse_rows


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
        from multiverso_tpu.tables import SparseMatrixTableOption
        from multiverso_tpu.tables.sparse_matrix_table import (
            SparseMatrixServerTable)
        if not hasattr(SparseMatrixServerTable, "read_buckets"):
            # before the world and the 4.6 GB table: a tree whose sparse
            # Get reads an exact, data-dependent shape compiles a program
            # a Get inside the window and cannot be warmed
            raise RuntimeError(
                "this tree's SparseMatrixServerTable has no read_buckets(): "
                "the cell needs a sparse Get whose compiled shapes are "
                "bounded and known before the window")
        cfg, mix = self.cfg, self.mix
        self.workers = int(mix["workers"])
        mv.MV_Init([f"-num_workers={self.workers}"]
                   + list(cfg.get("world_flags", [])))
        self.up = True
        self.mv = mv
        self.rows, self.cols = int(cfg["rows"]), int(cfg["cols"])
        self.table = mv.MV_CreateTable(SparseMatrixTableOption(
            num_rows=self.rows, num_cols=self.cols))
        rng = np.random.default_rng(self.seed)
        k, sets = int(mix["rows_per_verb"]), int(mix["id_sets_per_worker"])
        self.ids = [traffic.id_pool(rng, self.rows, k, mix["id_law"], sets)
                    for _ in range(self.workers)]
        self.deltas = [[traffic.whole_number_deltas(
            rng, (k, self.cols), int(mix["delta_low"]),
            int(mix["delta_high"]))
            for _ in range(int(mix["deltas_per_worker"]))]
            for _ in range(self.workers)]
        #: times worker w has added its id set j since the table was made
        #: (a worker writes only its own row of this)
        self.applied = np.zeros((self.workers, sets), np.int64)
        #: the id array of every Get of worker w (a worker appends only
        #: to its own list); the rows are checked for shape and dropped
        self.returned = [[] for _ in range(self.workers)]
        self._warm_shapes()
        self._drive(rounds=int(mix["warmup_rounds"]))
        # the record the freshness check reads starts where no worker has
        # anything stale: the warm-up's merges marked rows no Add of the
        # record explains
        self._drain()
        self.returned = [[] for _ in range(self.workers)]
        self.applied_at_start = self.applied.copy()

    def _delta(self, w: int, j: int) -> np.ndarray:
        return self.deltas[w][j % len(self.deltas[w])]

    def _warm_shapes(self) -> None:
        """How many rows a Get returns races the worker threads, and so
        does how many Adds one engine window merges: every shape either
        can meet is compiled here. The Gets: what is stale for a worker
        is whole Adds of other workers, so the table's ``read_stale``
        sees the id sets of 1, 2, ... Adds together (and one id, row 0,
        when there is none) up to ``READ_ROWS_CAP``, past which it reads
        by ``read_rows`` in pieces of the cap. The Adds: a lone Add
        through the verbs, the merges of 2 up to the number of workers
        through the server with zero deltas, as ``table_verbs`` does."""
        srv = self.table.server()
        k = int(self.mix["rows_per_verb"])
        with trace.span("bench.warm"):
            for n in [1] + list(range(k, srv.READ_ROWS_CAP + 1, k)):
                srv.read_stale(np.arange(n, dtype=np.int32) % self.rows)
            srv.read_rows(np.arange(srv.READ_ROWS_CAP + 1, dtype=np.int32)
                          % self.rows)
            zeros = np.zeros_like(self.deltas[0][0])
            with self.mv.MV_WorkerContext(0):
                self.table.AddRows(self.ids[0][0], zeros)
                self.table.Get()
            for n in range(2, self.workers + 1):
                srv.ProcessAddRun([{"row_ids": self.ids[w][0],
                                    "values": zeros, "option": None}
                                   for w in range(n)])

    def _drain(self) -> list:
        """One Get-all a worker, engine quiet: -> their (ids, rows)."""
        out = []
        for w in range(self.workers):
            with self.mv.MV_WorkerContext(w):
                out.append(self.table.Get())
        return out

    # -- the closed loop ----------------------------------------------------

    def _drive(self, seconds: float = 0.0, rounds: int = 0) -> dict:
        """Every worker loops Get-all, Add, Get-all until ``seconds`` have
        passed (or for ``rounds`` rounds). -> the window's record."""
        gate = threading.Barrier(self.workers + 1)
        lat_ms = [[] for _ in range(self.workers)]
        got_rows = [0] * self.workers
        failed = [0] * self.workers
        deadline = [0.0]
        k = int(self.mix["rows_per_verb"])

        def work(w: int) -> None:
            sets = len(self.ids[w])
            mine = self.returned[w]
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
                            ids_a, rows_a = self.table.Get()
                            t1 = time.perf_counter()
                        mine.append(ids_a)
                        with trace.span("bench.verb"):
                            self.table.AddRows(ids, delta)
                            t2 = time.perf_counter()
                        self.applied[w, j] += 1
                        with trace.span("bench.verb"):
                            ids_b, rows_b = self.table.Get()
                            t3 = time.perf_counter()
                        mine.append(ids_b)
                        failed[w] += sum(
                            rows.shape != (len(i), self.cols)
                            for i, rows in ((ids_a, rows_a),
                                            (ids_b, rows_b)))
                        got_rows[w] += len(ids_a) + len(ids_b)
                        lat_ms[w] += [(t1 - t0) * 1e3, (t2 - t1) * 1e3,
                                      (t3 - t2) * 1e3]
                    except Exception as exc:   # counted, and told once
                        if not failed[w]:
                            print(f"worker {w}: verb failed: {exc!r}",
                                  flush=True)
                        failed[w] += 3
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
        adds, gets = len(ops) // 3, 2 * (len(ops) // 3)
        return {"wall_s": sw.wall_s, "cpu_s": sw.cpu_s, "op_ms": ops,
                "rows": k * adds + sum(got_rows), "adds": adds,
                "gets": gets, "attempted": len(ops) + sum(failed),
                "failed": sum(failed),
                "notes": [f"{len(ops)} blocking verbs from {self.workers} "
                          f"workers, closed loop: {adds} AddRows of {k} "
                          f"rows, {gets} Gets that returned "
                          f"{sum(got_rows) / max(gets, 1):.0f} rows each; "
                          "ms at p50 / p95 / p99: Get before the Add "
                          + _quantiles(ops[0::3]) + ", AddRows "
                          + _quantiles(ops[1::3]) + ", Get after it "
                          + _quantiles(ops[2::3])]}

    def window(self, seconds: float, traced: bool) -> dict:
        return self._drive(seconds=float(self.mix["traced_seconds"])
                           if traced else seconds)

    # -- correctness --------------------------------------------------------

    def _adds(self, applied) -> list:
        return [(self.ids[w][j], self._delta(w, j), int(applied[w, j]))
                for w in range(self.workers)
                for j in range(len(self.ids[w]))]

    def _device_rows(self, ids: np.ndarray) -> np.ndarray:
        """Rows as the table holds them, by the server table's device
        plane, which does not touch the freshness state."""
        return np.asarray(self.table.server().device_fetch_rows(
            np.asarray(ids, np.int32)))

    def check(self) -> dict:
        ok, notes = [], []

        def note(passed: bool, what: str) -> None:
            ok.append(bool(passed))
            notes.append(("ok: " if passed else "FAILED: ") + what)

        # (iii) the engine is quiet: one more Get-all a worker returns the
        # table's final rows, and the Get-all after that row 0 alone
        last = self._drain()
        adds = self._adds(self.applied)
        for w, (ids, rows) in enumerate(last):
            self.returned[w].append(ids)
            # a Get's ids come ascending, each once: what the replay takes
            note(np.array_equal(rows, self._device_rows(ids))
                 and np.array_equal(rows, sparse_rows.replay_rows(
                     ids, self.cols, adds)),
                 f"worker {w}'s last Get-all returned {len(ids)} rows equal "
                 "to the table's and to the replay, bit for bit")
        again = [ids.tolist() for ids, _ in self._drain()]
        note(all(ids == [0] for ids in again),
             "the Get-all after that returned row 0 alone for every worker"
             + ("" if all(ids == [0] for ids in again)
                else f": {[len(ids) for ids in again]} rows"))
        # (ii) the runner's record of ids, order free
        since = self.applied - self.applied_at_start
        for w in range(self.workers):
            over, missed = sparse_rows.coverage(
                self.returned[w],
                [(self.ids[v][j], int(since[v, j]))
                 for v in range(self.workers) if v != w
                 for j in range(len(self.ids[v])) if since[v, j]],
                self.rows)
            note(over == 0 and missed == 0,
                 f"worker {w}'s {len(self.returned[w])} Gets returned "
                 f"{sum(map(len, self.returned[w]))} rows: {over} more "
                 f"often than other workers added them, {missed} added by "
                 "others and never returned")
        # (i) the values, as in mt_host_verbs
        rng = np.random.default_rng(self.seed + 1)
        n = int(self.cell.workload["sample_rows"])
        touched = np.unique(np.concatenate(
            [ids for per in self.ids for ids in per]))
        sample = np.sort(rng.choice(touched, min(n, len(touched)),
                                    replace=False)).astype(np.int32)
        note(np.array_equal(self._device_rows(sample),
                            sparse_rows.replay_rows(sample, self.cols, adds)),
             f"{len(sample)} sampled rows equal a numpy replay of the "
             f"{int(self.applied.sum())} Adds, bit for bit")
        free = np.setdiff1d(
            rng.integers(0, self.rows, 4 * n).astype(np.int32), touched)[:n]
        note(not np.any(self._device_rows(free)),
             f"{len(free)} sampled rows that no Add named are still zero")
        return {"correct": all(ok), "notes": notes}

    def close(self) -> None:
        if self.up:
            self.mv.MV_ShutDown()
            self.up = False
