"""Drives a set of MatrixTables through the device-plane row verbs, one
client in a closed loop: the WordEmbedding device-plane block round
without the train step.

A round fetches one id set from each table with ``device_fetch_rows``,
makes a delta on the device from the fetched rows and the round number,
applies it to the same rows with ``device_apply_rows`` and ends with
``block_until_ready`` on every table. Tables named ``input*`` take the
mix's input ids, the others its output ids. Deltas are small whole numbers
(the round's, times the table's), so the tables are checked bit for bit
against a numpy replay.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import trace, traffic
from benchmark.harness.run_record import Stopwatch
from benchmark.reference import table_replay


def _round_value(r: int) -> int:
    return 1 + r % 5


class Runner:
    def __init__(self, cell, seed: int, rehearsal: bool):
        self.cell, self.seed = cell, seed
        self.cfg, self.mix = cell.config, cell.traffic
        self.up = False

    def setup(self, workdir: str) -> None:
        import multiverso_tpu as mv
        from multiverso_tpu.tables import MatrixTableOption
        cfg, mix = self.cfg, self.mix
        mv.MV_Init(list(cfg.get("world_flags", [])))
        self.up, self.mv = True, mv
        self.rows, self.cols = int(cfg["rows"]), int(cfg["cols"])
        self.names = list(cfg["tables"])
        self.tables = [mv.MV_CreateTable(MatrixTableOption(
            num_rows=self.rows, num_cols=self.cols)) for _ in self.names]
        rng = np.random.default_rng(self.seed)
        sets = int(mix["id_sets"])
        self.pool = {
            "input": traffic.id_pool(rng, self.rows, int(mix["input_ids"]),
                                     mix["id_law"], sets),
            "output": traffic.id_pool(rng, self.rows, int(mix["output_ids"]),
                                      mix["id_law"], sets)}
        self.kinds = ["input" if n.startswith("input") else "output"
                      for n in self.names]
        self.rows_per_round = 2 * sum(
            len(self.pool[k][0]) for k in self.kinds)
        self.rounds_done = 0
        self._delta = jax.jit(
            lambda rows, value: (rows - rows) + value.astype(rows.dtype))
        for _ in range(int(mix["warmup_rounds"])):
            self._round()

    def _round(self) -> None:
        r = self.rounds_done
        j = r % len(self.pool["input"])
        servers = [t.server() for t in self.tables]
        with trace.span("bench.round"):
            fetched = [srv.device_fetch_rows(self.pool[kind][j])
                       for srv, kind in zip(servers, self.kinds)]
            for i, (srv, kind, rows) in enumerate(
                    zip(servers, self.kinds, fetched)):
                delta = self._delta(
                    rows, jnp.float32(_round_value(r) * (i + 1)))
                srv.device_apply_rows(self.pool[kind][j], delta)
            for srv in servers:
                srv.state["data"].block_until_ready()
        self.rounds_done = r + 1

    def window(self, seconds: float, traced: bool) -> dict:
        if traced:
            seconds = float(self.mix["traced_seconds"])
        first, lat_ms = self.rounds_done, []
        with Stopwatch() as sw:
            deadline = time.perf_counter() + seconds
            t0 = time.perf_counter()
            while t0 < deadline:
                self._round()
                t1 = time.perf_counter()
                lat_ms.append((t1 - t0) * 1e3)
                t0 = t1
        rounds = self.rounds_done - first
        return {"wall_s": sw.wall_s, "cpu_s": sw.cpu_s, "op_ms": lat_ms,
                "rows": rounds * self.rows_per_round,
                "attempted": rounds, "failed": 0,
                "notes": [f"{rounds} rounds of {self.rows_per_round} rows "
                          f"(fetched plus applied) over {len(self.tables)} "
                          f"tables, median round "
                          f"{float(np.median(lat_ms)):.3f} ms"]}

    def check(self) -> dict:
        rng = np.random.default_rng(self.seed + 1)
        n = int(self.cell.workload["sample_rows"])
        sets = len(self.pool["input"])
        weight = np.zeros(sets, np.int64)   # what each id set received
        for r in range(self.rounds_done):
            weight[r % sets] += _round_value(r)
        ok, notes = True, []
        for i, (name, table, kind) in enumerate(
                zip(self.names, self.tables, self.kinds)):
            touched = np.unique(np.concatenate(self.pool[kind]))
            sample = np.sort(rng.choice(touched, min(n, len(touched)),
                                        replace=False)).astype(np.int32)
            want = table_replay.expected_rows(
                sample, self.cols,
                [(ids, (i + 1) * int(weight[j]), 1)
                 for j, ids in enumerate(self.pool[kind])])
            exact = np.array_equal(table.GetRows(sample), want)
            free = np.setdiff1d(rng.integers(0, self.rows, 4 * n).astype(
                np.int32), touched)[:n]
            clean = not np.any(table.GetRows(free))
            ok = ok and exact and clean
            notes.append(
                ("ok: " if exact and clean else "FAILED: ")
                + f"table {name}: {len(sample)} sampled rows equal a numpy "
                f"replay of {self.rounds_done} rounds bit for bit "
                f"({exact}), {len(free)} rows no round named still zero "
                f"({clean})")
        return {"correct": ok, "notes": notes}

    def close(self) -> None:
        if self.up:
            self.mv.MV_ShutDown()
            self.up = False
