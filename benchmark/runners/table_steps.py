"""Drives a language model's vocabulary tables through the device-plane row
verbs as a trainer's steps do, one client in a closed loop.

The configuration's tables are MatrixTables under a server-side stateful
updater (``updater``), created with seeded initial rows. A step visits each
table in turn: ``device_fetch_rows`` of the step's ids, a delta made on the
device from the fetched rows, ``device_apply_rows`` of it on the same ids.
A table the mix lists under ``by_position`` is named by token id, one id
and one delta row per token *position*, repeats kept (an embedding and its
gradient); a table under ``whole_table`` by every id in order (an output
head under a full softmax). ``block_until_ready`` on every table's rows and
updater state ends the step.

Token ids: ranks log-uniform over [1, rows] (Zipf, s = 1), rank -> id by a
permutation drawn from the seed; ``id_sets`` steps of ids are drawn in
set-up and cycled. The delta is ``reference.adagrad_rows.delta_of`` of the
fetched row, the step and the column, computed on the device: a wrong fetch
shows in the tables, and a sampled row can be replayed alone.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import trace
from benchmark.harness.run_record import Stopwatch
from benchmark.reference import adagrad_rows


def token_ids(rng, rows: int, positions: int, perm: np.ndarray) -> np.ndarray:
    """``positions`` token ids, repeats kept: ranks log-uniform over
    [1, rows], the id of a rank by ``perm``."""
    ranks = np.floor(np.exp(rng.random(positions) * np.log(rows))).astype(
        np.int64) - 1
    return perm[np.clip(ranks, 0, rows - 1)].astype(np.int32)


class Runner:
    def __init__(self, cell, seed: int, rehearsal: bool):
        self.cell, self.seed = cell, seed
        self.cfg, self.mix = cell.config, cell.traffic
        self.up = False

    # -- set-up ------------------------------------------------------------

    def setup(self, workdir: str) -> None:
        import multiverso_tpu as mv
        from multiverso_tpu.tables import MatrixTableOption
        cfg, mix, work = self.cfg, self.mix, self.cell.workload
        mv.MV_Init(list(cfg.get("world_flags", [])))
        self.up, self.mv = True, mv
        self.rows, self.cols = int(cfg["rows"]), int(cfg["cols"])
        self.names = list(cfg["tables"])
        self.lr = float(mix["learning_rate"])
        assert cfg["updater"] == "adagrad", "the replay is AdaGrad's"
        rng = np.random.default_rng(self.seed)

        # the traffic first: the sample of rows to check is drawn from it
        positions = int(mix["sequences"]) * int(mix["sequence_tokens"])
        perm = rng.permutation(self.rows)
        self.pool = [token_ids(rng, self.rows, positions, perm)
                     for _ in range(int(mix["id_sets"]))]
        self.every = np.arange(self.rows, dtype=np.int32)
        self.by_position = [n in mix["by_position"] for n in self.names]
        assert all(p or n in mix["whole_table"]
                   for p, n in zip(self.by_position, self.names))
        self.unique = [len(np.unique(ids)) for ids in self.pool]
        n = int(work["sample_rows"])
        self.sample = [self._sample(rng, n, perm, by_pos)
                       for by_pos in self.by_position]

        std = float(cfg["init_std"])
        self.tables, self.init = [], []
        for sample in self.sample:
            full = rng.standard_normal((self.rows, self.cols),
                                       dtype=np.float32)
            full *= np.float32(std)
            self.init.append(full[sample].copy())
            self.tables.append(mv.MV_CreateTable(MatrixTableOption(
                num_rows=self.rows, num_cols=self.cols,
                updater_type=cfg["updater"],
                initializer=lambda shape, full=full: full)))
            del full

        @jax.jit
        def delta(rows, step, table):
            col = jnp.arange(rows.shape[1], dtype=jnp.int32)
            k = (step * 7 + col * 13 + table * 5) % 16
            pattern = (2 * k - 15).astype(jnp.float32) * jnp.float32(
                adagrad_rows.AMPLITUDE / 16)
            g = jnp.float32(adagrad_rows.SLOPE) * rows + pattern[None, :]
            return jnp.float32(self.lr) * g

        self._delta = delta
        self.steps_done = 0
        self.rows_per_step = 2 * sum(
            positions if p else self.rows for p in self.by_position)
        for _ in range(int(mix["warmup_steps"])):
            self._step()

    def _sample(self, rng, n: int, perm, by_position: bool) -> np.ndarray:
        """Sorted distinct rows to check. Of a table named by position, a
        third each: ids of rank 1 to 8,192, the 32 most frequent first
        (repeats by the thousand, then a few a step whose number changes
        from step to step: AdaGrad hides a repeat count that never
        changes), rows some step names, rows no step names."""
        if not by_position:
            return np.sort(rng.choice(self.rows, min(n, self.rows),
                                      replace=False)).astype(np.int32)
        named = np.unique(np.concatenate(self.pool))
        free = np.setdiff1d(self.every, named)
        top = min(8192, self.rows // 4)
        ranks = np.exp(rng.random(n // 3) * np.log(top / 32)) * 32
        often = perm[np.concatenate([np.arange(32), ranks.astype(np.int64)])]
        some = rng.choice(named, min(n // 3, len(named)), replace=False)
        idle = rng.choice(free, min(n // 3, len(free)), replace=False)
        return np.unique(np.concatenate([often, some, idle])).astype(np.int32)

    # -- the window --------------------------------------------------------

    def _step(self) -> None:
        s = self.steps_done
        step = jnp.int32(s)
        servers = [t.server() for t in self.tables]
        # the guarantee: nothing the device plane is handed or hands back
        # leaves the device (a program that copies a delta to the host to
        # combine its repeats raises here; the CPU of a rehearsal has no
        # such transfer to forbid)
        with trace.span("bench.step"), \
                jax.transfer_guard_device_to_host("disallow_explicit"):
            for i, (srv, by_pos) in enumerate(zip(servers,
                                                  self.by_position)):
                ids = self.pool[s % len(self.pool)] if by_pos else self.every
                rows = srv.device_fetch_rows(ids)
                delta = self._delta(rows, step, jnp.int32(i))
                del rows        # the fetched copy is not held over the apply
                srv.device_apply_rows(ids, delta)
                del delta
            for srv in servers:     # rows and updater state
                jax.block_until_ready(srv.state)
        self.steps_done = s + 1

    def window(self, seconds: float, traced: bool) -> dict:
        if traced:
            seconds = float(self.mix["traced_seconds"])
        first, lat_ms = self.steps_done, []
        with Stopwatch() as sw:
            deadline = time.perf_counter() + seconds
            t0 = time.perf_counter()
            while t0 < deadline:
                self._step()
                t1 = time.perf_counter()
                lat_ms.append((t1 - t0) * 1e3)
                t0 = t1
        steps = self.steps_done - first
        median = float(np.median(lat_ms))
        slow = [i for i, ms in enumerate(lat_ms) if ms > 2 * median]
        return {"wall_s": sw.wall_s, "cpu_s": sw.cpu_s, "op_ms": lat_ms,
                "rows": steps * self.rows_per_step,
                "attempted": steps, "failed": 0,
                "row_verbs": self._row_verbs(first, self.steps_done),
                "notes": [f"{steps} steps of {self.rows_per_step} rows "
                          f"(fetched plus applied) over {len(self.tables)} "
                          f"tables, median step {median:.3f} ms; a step "
                          f"names {int(np.mean(self.unique))} distinct rows "
                          f"in {len(self.pool[0])} positions",
                          # a closed loop stands still while its host does
                          f"{len(slow)} steps took over twice the median"
                          + (f": the slowest {max(lat_ms):.1f} ms, at step "
                             f"{int(np.argmax(lat_ms))} of the window; "
                             f"{sum(lat_ms[i] for i in slow):.1f} ms in all"
                             if slow else "")]}

    def _row_verbs(self, first: int, last: int) -> list:
        """What the window's verbs named, for the byte count of
        ``layer_metrics/row_plane_roofline.py``: one entry a table and verb
        with the positions and the distinct rows, summed over the steps."""
        positions = unique = 0
        for s in range(first, last):
            positions += len(self.pool[s % len(self.pool)])
            unique += self.unique[s % len(self.pool)]
        steps = last - first
        row_bytes = self.cols * 4
        state = 2       # what an apply reads and writes a row: it, its history
        out = []
        for by_pos in self.by_position:
            p, u = ((positions, unique) if by_pos
                    else (steps * self.rows, steps * self.rows))
            out.append({"verb": "fetch", "positions": p, "unique": u,
                        "row_bytes": row_bytes, "state": state})
            out.append({"verb": "apply", "positions": p, "unique": u,
                        "row_bytes": row_bytes, "state": state})
        return out

    # -- the check ---------------------------------------------------------

    def check(self) -> dict:
        tol = self.cell.workload["tolerance"]
        sets = len(self.pool)
        ok, notes = True, []
        for i, (name, table, by_pos, sample, init) in enumerate(zip(
                self.names, self.tables, self.by_position, self.sample,
                self.init)):
            if by_pos:
                per_set = np.stack([np.bincount(ids, minlength=self.rows)[
                    sample] for ids in self.pool])
            else:
                per_set = np.ones((sets, len(sample)), np.int64)
            counts = [per_set[s % sets] for s in range(self.steps_done)]
            want = self._replay(init, counts, i)
            device = np.asarray(table.server().device_fetch_rows(sample))
            host = table.GetRows(sample)
            same = np.array_equal(device, host)
            idle = per_set.sum(axis=0) == 0
            kept = np.array_equal(host[idle], init[idle])
            err = np.abs(host.astype(np.float64) - want)
            worst = float(err.max())
            share = float(np.mean(err <= float(tol["entry_abs"])))
            good = (same and kept and worst <= float(tol["worst_abs"])
                    and share >= float(tol["entry_share"]))
            ok = ok and good
            notes.append(
                ("ok: " if good else "FAILED: ")
                + f"table {name}: {len(sample)} sampled rows against the "
                f"plain replay of {self.steps_done} steps: worst entry "
                f"{worst:.3e} off (limit {tol['worst_abs']}), "
                f"{100 * share:.4f} % within {tol['entry_abs']} (at least "
                f"{100 * float(tol['entry_share'])} %); the device fetch "
                f"and the host Get agree bit for bit ({same}); "
                f"{int(idle.sum())} rows no step named keep their initial "
                f"values bit for bit ({kept})")
        return {"correct": ok, "notes": notes}

    def _replay(self, init, counts, table: int) -> np.ndarray:
        """The plain replay of the sampled rows, a few hundred rows at a
        time on a handful of threads: a row depends on itself alone, and
        numpy leaves the interpreter lock for each pass over a block."""
        blocks = np.array_split(np.arange(len(init)),
                                max(1, len(init) // 256))
        with ThreadPoolExecutor(8) as pool:
            done = pool.map(lambda b: adagrad_rows.replay(
                init[b], [c[b] for c in counts], table,
                learning_rate=self.lr)[0], blocks)
            return np.concatenate(list(done))

    def close(self) -> None:
        if self.up:
            self.mv.MV_ShutDown()
            self.up = False
