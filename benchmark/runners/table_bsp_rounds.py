"""Drives one MatrixTable under the BSP server (``-sync=true``,
``SyncServer``) from worker threads in lock step: the traffic of the
reference's ``Test/test_matrix_perf.cpp`` held to the guarantee of
``Test/unittests/test_sync.cpp``.

In round ``r`` every worker, under ``MV_WorkerContext``, does a blocking
``AddRows`` of its own delta to ONE shared set of distinct rows and then a
blocking ``GetRows`` of the same rows. The server's vector clocks count
every Get and Add of every worker from ``MV_Init`` on, so the workers end
every stretch on the same clock: the warm-up is whole rounds by all of
them (no lone verb, no call on the server object), a window is a fixed
count of rounds, the same for every worker, and the last reads are made
by all of them too. One worker a verb ahead would leave the next window's
first Get waiting for ever.

Every Get of every round since the table was made is kept (one row in
``keep_every``, a copy) and held to the plain round-by-round replay
(``benchmark/reference/bsp_rounds.py``) bit for bit: deltas are whole
numbers, so a round's sum does not depend on the order the server applied
its Adds in. Every join has a time limit that fails the run: a protocol
fault shows as a Get that never returns.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from benchmark.harness import clock, trace, traffic
from benchmark.harness.run_record import Stopwatch
from benchmark.reference import bsp_rounds

#: seconds a stretch of rounds, the last reads or the shutdown may take
#: before the run is failed: a window is about 10 s, a first run's
#: warm-up compiles for under a minute
JOIN_LIMIT_S = 300.0


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
        self.k = int(mix["rows_per_verb"])
        #: the id set of round r is sets[r % len(sets)], for every worker
        self.sets = traffic.id_pool(rng, self.rows, self.k, mix["id_law"],
                                    int(mix["shared_id_sets"]))
        #: worker w's delta of round r is deltas[w][r % len(deltas[w])]
        self.deltas = [[traffic.whole_number_deltas(
            rng, (self.k, self.cols), int(mix["delta_low"]),
            int(mix["delta_high"]))
            for _ in range(int(mix["deltas_per_worker"]))]
            for _ in range(self.workers)]
        #: rounds run since the table was made: every worker's clock
        self.rounds_done = 0
        #: kept[w][r]: one row in keep_every of worker w's Get of round r
        #: (a worker appends only to its own list)
        self.kept = [[] for _ in range(self.workers)]
        self._drive(int(mix["warmup_rounds"]))

    def _ids(self, r: int) -> np.ndarray:
        return self.sets[r % len(self.sets)]

    def _delta(self, w: int, r: int) -> np.ndarray:
        return self.deltas[w][r % len(self.deltas[w])]

    def _start(self, work) -> list:
        """``work(w)`` on a thread a worker. -> the started threads."""
        threads = [threading.Thread(target=work, args=(w,), daemon=True,
                                    name=f"worker {w}")
                   for w in range(self.workers)]
        for t in threads:
            t.start()
        return threads

    def _join(self, threads, what: str) -> None:
        """Wait for the worker threads, together at most JOIN_LIMIT_S."""
        until = time.monotonic() + JOIN_LIMIT_S
        for t in threads:
            t.join(max(0.0, until - time.monotonic()))
        stuck = [t.name for t in threads if t.is_alive()]
        if stuck:
            raise RuntimeError(
                f"{what}: {', '.join(stuck)} did not return within "
                f"{JOIN_LIMIT_S:g} s: a verb that the BSP server never "
                "answered")

    # -- rounds in lock step ------------------------------------------------

    def _drive(self, rounds: int) -> dict:
        """Every worker runs the next ``rounds`` rounds, Add then Get.
        -> the stretch's record."""
        first = self.rounds_done
        every = int(self.mix["keep_every"])
        gate = threading.Barrier(self.workers + 1)
        lat_ms = [[] for _ in range(self.workers)]
        failed = [0] * self.workers

        def work(w: int) -> None:
            mine = self.kept[w]
            with self.mv.MV_WorkerContext(w):
                gate.wait(JOIN_LIMIT_S)
                for r in range(first, first + rounds):
                    ids, delta = self._ids(r), self._delta(w, r)
                    try:
                        with trace.span("bench.verb"):
                            t0 = time.perf_counter()
                            self.table.AddRows(ids, delta)
                            t1 = time.perf_counter()
                        with trace.span("bench.verb"):
                            got = self.table.GetRows(ids)
                            t2 = time.perf_counter()
                        mine.append(got[::every].copy())
                        if got.shape != delta.shape:
                            failed[w] += 1
                        lat_ms[w] += [(t1 - t0) * 1e3, (t2 - t1) * 1e3]
                    except Exception as exc:   # counted, and told once
                        if not failed[w]:
                            print(f"worker {w}: a verb of round {r} failed: "
                                  f"{exc!r}", flush=True)
                        failed[w] += 2
                        mine.append(None)

        threads = self._start(work)
        with Stopwatch() as sw:
            gate.wait(JOIN_LIMIT_S)
            self._join(threads, f"rounds {first} to {first + rounds - 1}")
        self.rounds_done = first + rounds
        ops = [x for per in lat_ms for x in per]
        return {"wall_s": sw.wall_s, "cpu_s": sw.cpu_s, "op_ms": ops,
                "rows": self.k * len(ops), "adds": len(ops) // 2,
                "gets": len(ops) // 2, "rounds": rounds,
                "attempted": len(ops) + sum(failed), "failed": sum(failed),
                "notes": [f"{rounds} rounds in lock step: {len(ops)} "
                          f"blocking verbs of {self.k} rows from "
                          f"{self.workers} workers; ms at p50 / p95 / p99: "
                          "AddRows " + _quantiles(ops[0::2]) + ", GetRows "
                          + _quantiles(ops[1::2])]}

    def window(self, seconds: float, traced: bool) -> dict:
        per_round = 2 * self.k * self.workers
        rounds = max(8, round(
            seconds * float(self.cell.workload["nominal_rows_per_s"])
            / per_round))
        return self._drive(max(2, rounds // 5) if traced else rounds)

    # -- correctness --------------------------------------------------------

    def _last_reads(self, samples) -> list:
        """Every worker reads every sample, in the same order, so that all
        end on the same clock. -> [worker][sample] rows."""
        out = [[None] * len(samples) for _ in range(self.workers)]

        def work(w: int) -> None:
            with self.mv.MV_WorkerContext(w):
                for i, ids in enumerate(samples):
                    out[w][i] = self.table.GetRows(ids).copy()

        self._join(self._start(work), "the last reads")
        return out

    def check(self) -> dict:
        ok, notes = [], []

        def note(passed: bool, what: str) -> None:
            ok.append(bool(passed))
            notes.append(("ok: " if passed else "FAILED: ") + what)

        every = int(self.mix["keep_every"])
        ref = bsp_rounds.BspRounds(self.cols, self.workers,
                                   np.concatenate(self.sets))
        # (i) every kept Get of every round since the table was made
        off = uneven = None
        for r in range(self.rounds_done):
            ids = self._ids(r)
            ref.round(r, ids, [self._delta(w, r)
                               for w in range(self.workers)])
            want = ref.expect_get(r, ids)[::every]
            gets = [self.kept[w][r] if r < len(self.kept[w]) else None
                    for w in range(self.workers)]
            for w, got in enumerate(gets):
                if off is None and (got is None
                                    or not np.array_equal(got, want)):
                    off = (w, r)
                if uneven is None and (got is None or gets[0] is None
                                       or not np.array_equal(got, gets[0])):
                    uneven = (w, r)
        total = self.workers * self.rounds_done
        note(off is None,
             f"the {total} Gets of {self.rounds_done} rounds (warm-up "
             f"included, one row in {every} kept) each equal the "
             "round-by-round replay of ALL workers' Adds up to its round "
             "and of no later one, bit for bit"
             + ("" if off is None else
                f": worker {off[0]}'s Get of round {off[1]} is the first "
                "that does not"))
        note(uneven is None,
             "the Gets of a round are equal, bit for bit"
             + ("" if uneven is None else
                f": worker {uneven[0]}'s Get of round {uneven[1]} differs "
                "from worker 0's"))
        # (ii) the table when the window has ended, read by all workers
        rng = np.random.default_rng(self.seed + 1)
        n = int(self.cell.workload["sample_rows"])
        sample = np.sort(rng.choice(ref.ids, min(n, len(ref.ids)),
                                    replace=False)).astype(np.int32)
        free = np.setdiff1d(rng.integers(0, self.rows, 4 * n).astype(
            np.int32), ref.ids)[:n]
        reads = self._last_reads([sample, free])
        want = ref.table_rows(sample)
        note(all(np.array_equal(reads[w][0], want)
                 for w in range(self.workers)),
             f"{len(sample)} sampled rows, read by all {self.workers} "
             f"workers, equal the replay of the {total} acknowledged Adds, "
             "bit for bit")
        note(not any(np.any(reads[w][1]) for w in range(self.workers)),
             f"{len(free)} sampled rows that no Add named are still zero")
        return {"correct": all(ok), "notes": notes}

    def close(self) -> None:
        """``MV_ShutDown``: FinishTrain drains a worker that stopped
        ahead. Under a time limit: a drain that never ends fails the run
        here and does not hang it."""
        if not self.up:
            return
        self.up = False
        done = threading.Thread(target=self.mv.MV_ShutDown, daemon=True,
                                name="MV_ShutDown")
        done.start()
        done.join(JOIN_LIMIT_S)
        if done.is_alive():
            print(f"FAIL: MV_ShutDown did not return within "
                  f"{JOIN_LIMIT_S:g} s", flush=True)
            os._exit(1)
