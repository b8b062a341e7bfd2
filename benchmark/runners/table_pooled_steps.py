"""Drives a recommendation trainer's embedding tables through the POOLED
device-plane verbs as a row-sharded deployment's steps do, one client in a
closed loop.

The configuration's tables (``rows``: server ``server``'s block of each of
the source's tables, ``cols`` wide) are MatrixTables under the server-side
stateful updater ``updater``, created with seeded initial rows. A step
visits every table in the configuration's order: ``device_fetch_pooled``
of the table's jagged bags (ids bag after bag, and their lengths; the
bags' rung returned as it is), a gradient a bag made on the device from
the pooled rows, ``device_apply_pooled`` of it on the same bags under the
mix's learning rate. ``block_until_ready`` on every table's rows and
updater state ends the step.

The bags (``jagged_bags``): a table's positions are ``rec_bag_steps``' own,
``bags * multi_hot_sizes[t]`` ids by ``table_bag_steps.bag_ids``' law
(``bags`` of them log-uniform through a permutation drawn from the seed,
the rest uniform over the share's rows). Every position is given a sample
uniform over the mix's ``batch``; the positions are sorted by sample
(stable) and the bags are the samples present, so no empty bag is sent.
``id_sets`` steps of bags are drawn in set-up and cycled. The program
compiles a pooled fetch for every (position rung, bag rung) and a pooled
apply for every (position rung, bag rung, distinct class)
(``multiverso_tpu.tables.pooled.program_key``), so the warm-up runs the
sets that between them hold every key each table meets in its ``id_sets``
sets (reckoned from the sets): nothing compiles inside the window.

The gradient is ``reference.adagrad_rows.delta_of``'s law on the pooled
row, clipped and under a slope of its own (``reference.
pooled_adagrad_rows.SLOPE``: the pattern's sign is the gradient's), the
step, the column and the table, computed on the device: a wrong pool, or a
gradient that reaches a neighbouring table, shows in the tables. A bag's
gradient depends on every row of the bag, so ``check`` replays, on the
host, every step since the tables were made over every row that some id
set names (``reference.pooled_adagrad_rows``; the named rows renumbered
0..m-1, their seeded initial values kept on the host, a band of columns a
thread for the large tables: every column replays alone).
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import trace
from benchmark.harness.run_record import Stopwatch
from benchmark.reference import adagrad_rows, pooled_adagrad_rows
from benchmark.runners.table_bag_steps import (bag_ids, covering_sets,
                                               sample_quota, sample_rows)

#: columns a band of the replay, for tables of more named rows than this
BAND_COLS, BAND_ROWS = 32, 100_000


def jagged_bags(rng, rows: int, bags: int, hot: int, perm: np.ndarray,
                batch: int):
    """One table's bags of a step as server ``server`` meets them: (ids
    bag after bag, lengths). Exactly ``bags * hot`` positions by
    ``bag_ids``' law; each position's sample uniform over ``batch``; the
    positions sorted by sample (stable); a bag a sample present."""
    ids = bag_ids(rng, rows, bags, hot, perm)
    sample = rng.integers(0, batch, len(ids))
    order = np.argsort(sample, kind="stable")       # the one sort
    sample = sample[order]
    edges = np.flatnonzero(np.r_[True, sample[1:] != sample[:-1], True])
    return ids[order], np.diff(edges).astype(np.int32)


def sample_bags(rng, lengths: np.ndarray, quota: int):
    """(positions, lengths) of up to ``quota`` of a table's bags, the
    longest among them."""
    if len(lengths) > quota:
        longest = np.argsort(-lengths, kind="stable")[:quota // 4]
        rest = rng.choice(np.setdiff1d(np.arange(len(lengths)), longest),
                          quota - len(longest), replace=False)
        picked = np.sort(np.concatenate([longest, rest]))
    else:
        picked = np.arange(len(lengths))
    starts = np.cumsum(lengths) - lengths
    where = np.concatenate([np.arange(starts[b], starts[b] + lengths[b])
                            for b in picked])
    return where, lengths[picked]


class Runner:
    def __init__(self, cell, seed: int, rehearsal: bool):
        self.cell, self.seed = cell, seed
        self.cfg, self.mix = cell.config, cell.traffic
        self.up = False

    # -- set-up ------------------------------------------------------------

    def setup(self, workdir: str) -> None:
        import multiverso_tpu as mv
        from multiverso_tpu.tables import MatrixTableOption
        from multiverso_tpu.tables.matrix_table import MatrixServerTable
        from multiverso_tpu.updaters.base import AddOption
        if not hasattr(MatrixServerTable, "device_fetch_pooled"):
            raise RuntimeError(
                "this program's MatrixServerTable has no device_fetch_pooled"
                " / device_apply_pooled: it cannot run a configuration "
                "whose server pools")
        from multiverso_tpu.tables.pooled import program_key
        cfg, mix, work = self.cfg, self.mix, self.cell.workload
        mv.MV_Init(list(cfg.get("world_flags", [])))
        self.up, self.mv = True, mv
        self.rows = [int(r) for r in cfg["rows"]]
        self.cols = int(cfg["cols"])
        published = cfg["published"]
        self.hot = [int(h) for h in published["multi_hot_sizes"]]
        assert len(self.rows) == len(self.hot) == len(
            published["num_embeddings_per_feature"])
        assert cfg["updater"] == "adagrad", "the replay is AdaGrad's"
        self.lr, self.rho = float(mix["learning_rate"]), float(mix["rho"])
        self.option = AddOption(learning_rate=self.lr, rho=self.rho)
        bags, sets = int(mix["bags"]), int(mix["id_sets"])
        batch = int(mix["batch"])
        rng = np.random.default_rng(self.seed)

        # the traffic first: the sample of rows to check is drawn from it
        perms = [rng.permutation(r) for r in self.rows]
        self.pool = [[jagged_bags(rng, r, bags, h, p, batch)
                      for r, h, p in zip(self.rows, self.hot, perms)]
                     for _ in range(sets)]
        self.unique = [[len(np.unique(ids)) for ids, _ in row]
                       for row in self.pool]
        # the rows some id set names: the replay's rows, renumbered
        self.named = [np.unique(np.concatenate([row[t][0]
                                                for row in self.pool]))
                      for t in range(len(self.rows))]
        quota = sample_quota(self.rows, int(work["sample_rows"]))
        self.sample = [sample_rows(rng, r, quota, perms[t], self.named[t])
                       for t, r in enumerate(self.rows)]

        self.tables, self.init, self.host = [], [], []
        for r, total, sample, named in zip(
                self.rows, published["num_embeddings_per_feature"],
                self.sample, self.named):
            full = rng.random((r, self.cols), dtype=np.float32)
            full = (2 * full - 1) * np.float32(1 / np.sqrt(total))
            self.init.append(full[sample].copy())
            # the replay's start, kept on the host: the named rows, a band
            # of columns a thread for the large tables
            width = BAND_COLS if len(named) > BAND_ROWS else self.cols
            self.host.append([
                (np.arange(c, c + width),
                 np.ascontiguousarray(full[named, c: c + width]))
                for c in range(0, self.cols, width)])
            # the rows are handed over once: the host keeps the replay's
            # copy of the named rows, not a second whole table
            once = [full]
            del full
            self.tables.append(mv.MV_CreateTable(MatrixTableOption(
                num_rows=r, num_cols=self.cols, updater_type=cfg["updater"],
                initializer=lambda shape, once=once: once.pop())))
        self.servers = [t.server() for t in self.tables]

        lr = self.lr

        @jax.jit
        def delta(pooled, step, table):
            col = jnp.arange(pooled.shape[1], dtype=jnp.int32)
            k = (step * 7 + col * 13 + table * 5) % 16
            pattern = (2 * k - 15).astype(jnp.float32) * jnp.float32(
                adagrad_rows.AMPLITUDE / 16)
            bound = jnp.float32(pooled_adagrad_rows.BOUND)
            g = (jnp.float32(pooled_adagrad_rows.SLOPE)
                 * jnp.clip(pooled, -bound, bound) + pattern[None, :])
            return jnp.float32(lr) * g

        self._delta = delta
        # a scalar's copy to the device is a dispatch of its own: the
        # table numbers are placed once, the step once a step
        self.table_no = [jax.device_put(np.int32(t))
                         for t in range(len(self.rows))]
        self.history = []        # the id set of every step since creation
        self.keys = [[program_key(len(ids), len(lengths), n)
                      for (ids, lengths), n in zip(row, distinct)]
                     for row, distinct in zip(self.pool, self.unique)]
        cover = covering_sets(self.keys)
        self.warmup_sets = cover + cover[:1] * max(
            0, int(mix["warmup_steps_min"]) - len(cover))
        for k in self.warmup_sets:
            self._step(k)

    # -- the window --------------------------------------------------------

    def _step(self, k: int) -> None:
        step = jax.device_put(np.int32(len(self.history)))
        # the guarantee: nothing the device plane is handed or hands back
        # leaves the device (the CPU of a rehearsal has no such transfer
        # to forbid)
        with trace.span("bench.step"), \
                jax.transfer_guard_device_to_host("disallow_explicit"):
            for srv, (ids, lengths), number in zip(
                    self.servers, self.pool[k], self.table_no):
                pooled = srv.device_fetch_pooled(ids, lengths, padded=True)
                grads = self._delta(pooled, step, number)
                del pooled      # the fetched sums are not held over the apply
                srv.device_apply_pooled(ids, lengths, grads, self.option)
                del grads
            for srv in self.servers:     # rows and updater state
                jax.block_until_ready(srv.state)
        self.history.append(k)

    def window(self, seconds: float, traced: bool) -> dict:
        if traced:
            seconds = float(self.mix["traced_seconds"])
        first, lat_ms, sets = len(self.history), [], len(self.pool)
        with Stopwatch() as sw:
            deadline = time.perf_counter() + seconds
            t0 = time.perf_counter()
            while t0 < deadline:
                self._step(len(self.history) % sets)
                t1 = time.perf_counter()
                lat_ms.append((t1 - t0) * 1e3)
                t0 = t1
        done = self.history[first:]
        verbs = self._pooled_verbs(done)
        positions = sum(v["positions"] for v in verbs)
        bags = sum(v["bags"] for v in verbs)
        steps = max(1, len(done))
        median = float(np.median(lat_ms))
        slow = [i for i, ms in enumerate(lat_ms) if ms > 2 * median]
        return {"wall_s": sw.wall_s, "cpu_s": sw.cpu_s, "op_ms": lat_ms,
                "rows": 2 * positions,
                "attempted": len(done), "failed": 0,
                "verbs": len(done) * 2 * len(self.tables),
                "pooled_verbs": verbs,
                "notes": [f"{len(done)} steps of {2 * positions / steps:.0f} "
                          f"rows (positions pooled plus positions applied) "
                          f"in {2 * len(self.tables)} verbs over "
                          f"{len(self.tables)} tables, median step "
                          f"{median:.3f} ms; a step names "
                          f"{int(np.mean(np.sum(self.unique, axis=1)))} "
                          f"distinct rows in {positions / steps:.0f} "
                          f"positions of {bags / steps:.0f} bags "
                          f"({positions / max(1, bags):.3f} positions a "
                          f"bag); the warm-up ran id sets "
                          f"{self.warmup_sets} for "
                          f"{len({k for row in self.keys for k in row})} "
                          f"program keys",
                          f"{len(slow)} steps took over twice the median"
                          + (f": the slowest {max(lat_ms):.1f} ms, at step "
                             f"{int(np.argmax(lat_ms))} of the window; "
                             f"{sum(lat_ms[i] for i in slow):.1f} ms in all"
                             if slow else "")]}

    def _pooled_verbs(self, done) -> list:
        """What the window's verbs named, for the byte count of
        ``layer_metrics/pooled_plane_roofline.py``: one entry a table with
        the positions, the bags and the distinct rows, summed over the
        steps (each step one pooled fetch and one pooled apply of them)."""
        used = np.bincount(done, minlength=len(self.pool))
        out = []
        for t in range(len(self.tables)):
            out.append({
                "positions": int(sum(n * len(self.pool[k][t][0])
                                     for k, n in enumerate(used))),
                "bags": int(sum(n * len(self.pool[k][t][1])
                                for k, n in enumerate(used))),
                "unique": int(sum(n * self.unique[k][t]
                                  for k, n in enumerate(used))),
                # state 2: an apply reads and writes the row and its history
                "row_bytes": self.cols * 4, "state": 2})
        return out

    # -- the check ---------------------------------------------------------

    def _replay(self) -> float:
        """Every named row of every table after every step of ``history``,
        on the host: the kept initial rows advanced in place. -> seconds
        it took."""
        t0 = time.perf_counter()
        plans = {}

        def make_plan(key):
            k, t = key
            ids, lengths = self.pool[k][t]
            plans[key] = pooled_adagrad_rows.plan(
                np.searchsorted(self.named[t], ids), lengths)

        def chain(job):
            t, columns, w = job
            h, scratch = np.zeros_like(w), {}
            for step, k in enumerate(self.history):
                pooled_adagrad_rows.advance(
                    w, h, plans[k, t], step, t, scratch, columns=columns,
                    learning_rate=self.lr, rho=self.rho)

        jobs = [(t, columns, w) for t, bands in enumerate(self.host)
                for columns, w in bands]
        jobs.sort(key=lambda job: -job[2].size)     # the longest first
        keys = sorted({(k, t) for k in self.history
                       for t in range(len(self.tables))},
                      key=lambda key: -len(self.pool[key[0]][key[1]][0]))
        with ThreadPoolExecutor(max(1, (os.cpu_count() or 2) - 1)) as ex:
            list(ex.map(make_plan, keys))
            list(ex.map(chain, jobs))
        return time.perf_counter() - t0

    def check(self) -> dict:
        tol = self.cell.workload["tolerance"]
        ok, notes, errs = True, [], []
        took = self._replay()
        notes.append(f"the plain replay of {len(self.history)} steps over "
                     f"the {sum(len(n) for n in self.named)} rows some id "
                     f"set names (of {sum(self.rows)}) took {took:.1f} s, "
                     f"outside the window and outside setup_s")
        last = self.pool[self.history[-1]]
        rng = np.random.default_rng(self.seed + 1)
        bag_quota = sample_quota([len(lengths) for _, lengths in last],
                                 int(self.cell.workload["sample_bags"]))
        bag_worst, bag_count = 0.0, 0
        for t, (table, sample, init, named) in enumerate(zip(
                self.tables, self.sample, self.init, self.named)):
            srv = table.server()
            device = np.asarray(srv.device_fetch_rows(sample))
            host = table.GetRows(sample)
            same = np.array_equal(device, host)
            at = np.searchsorted(named, sample)
            hit = named[np.minimum(at, len(named) - 1)] == sample
            want = init.copy()
            want[hit] = np.concatenate([w[at[hit]] for _, w in self.host[t]],
                                       axis=1)
            kept = np.array_equal(host[~hit], init[~hit])
            err = np.abs(host.astype(np.float64) - want)
            errs.append(err.ravel())
            # bags of the last step, pooled by the device after the
            # window, against the host's sum of the rows GetRows returns
            ids, lengths = last[t]
            where, sub = sample_bags(rng, lengths, bag_quota)
            pooled = np.asarray(srv.device_fetch_pooled(ids[where], sub))
            uniq, inv = np.unique(ids[where], return_inverse=True)
            summed = pooled_adagrad_rows.pool(table.GetRows(uniq), inv, sub)
            off = float(np.abs(pooled.astype(np.float64) - summed).max())
            bag_worst, bag_count = max(bag_worst, off), bag_count + len(sub)
            good = (same and kept and pooled.shape == summed.shape
                    and float(err.max()) <= float(tol["worst_abs"])
                    and off <= float(tol["pooled_abs"]))
            ok = ok and good
            notes.append(
                ("ok: " if good else "FAILED: ")
                + f"table {t} ({self.rows[t]} rows, {len(named)} named): "
                f"{len(sample)} sampled rows against the plain replay of "
                f"{len(self.history)} steps: worst entry "
                f"{float(err.max()):.3e} off (limit {tol['worst_abs']}), "
                f"{100 * float(np.mean(err <= float(tol['entry_abs']))):.4f}"
                f" % within {tol['entry_abs']}; device fetch and host Get "
                f"agree bit for bit ({same}); {int((~hit).sum())} rows no "
                f"step named keep their initial values bit for bit ({kept})"
                f"; {len(sub)} bags of the last step pooled by the device "
                f"{off:.3e} off the host's sum of their rows (limit "
                f"{tol['pooled_abs']})")
        errs = np.concatenate(errs)
        share = float(np.mean(errs <= float(tol["entry_abs"])))
        enough = share >= float(tol["entry_share"])
        notes.append(
            ("ok: " if enough else "FAILED: ")
            + f"all {len(self.tables)} tables: {len(errs) // self.cols} "
            f"sampled rows, worst entry {float(errs.max()):.3e} off, "
            f"{100 * share:.4f} % of entries within {tol['entry_abs']} (at "
            f"least {100 * float(tol['entry_share'])} %); {bag_count} "
            f"sampled bags, the worst {bag_worst:.3e} off")
        return {"correct": ok and enough, "notes": notes}

    def close(self) -> None:
        if self.up:
            self.mv.MV_ShutDown()
            self.up = False
