"""Drives a recommendation trainer's embedding tables through the
device-plane row verbs as its steps do, one client in a closed loop.

The configuration's tables (``rows``: one row count a table, ``cols``
wide) are MatrixTables under the server-side stateful updater ``updater``,
created with seeded initial rows. A step visits every table in the
configuration's order: ``device_fetch_rows`` of the table's ids of the
step, a delta made on the device from the fetched rows,
``device_apply_rows`` of it on the same ids under the mix's learning rate.
A table's ids are ``bags`` bags of ``multi_hot_sizes[t]`` ids, one id and
one delta row a position, repeats kept. ``block_until_ready`` on every
table's rows and updater state ends the step.

Ids: the first of a bag has a rank log-uniform over [1, rows] (rank -> row
by a permutation drawn from the seed), the others are uniform over the
table's rows. ``id_sets`` steps of ids are drawn in set-up and cycled. The
program compiles a row program a table for every power-of-two class of the
distinct-row count of a verb's ids (and another where no id repeats), so
the warm-up runs the sets that between them hold every class each table
meets in its ``id_sets`` sets (reckoned from the sets, ``distinct_class``):
nothing compiles inside the window. The delta is
``reference.adagrad_rows.delta_of`` of the fetched row, the step, the
column and the table, computed on the device: a wrong fetch, or a delta
that reaches a neighbouring table, shows in the tables, and a sampled row
can be replayed alone.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import trace
from benchmark.harness.run_record import Stopwatch
from benchmark.reference import adagrad_rows


def bag_ids(rng, rows: int, bags: int, hot: int, perm: np.ndarray):
    """``bags * hot`` row ids below ``rows``, bag after bag: the first id
    of a bag by a log-uniform rank through ``perm`` (rank ``r`` of 1 to
    ``rows`` with probability log((r + 1) / r) / log(rows + 1), so that a
    table of two rows has both named), the rest uniform."""
    ranks = np.floor(np.exp(rng.random(bags) * np.log(rows + 1))).astype(
        np.int64) - 1
    ids = rng.integers(0, rows, (bags, hot))
    ids[:, 0] = perm[np.clip(ranks, 0, rows - 1)]
    return ids.astype(np.int32).ravel()


def distinct_class(positions: int, distinct: int):
    """What of a verb's ids picks its apply's program: whether any repeats
    (the combine's program or the plain row update), and the power of two
    the distinct-row count rounds up to, as an exponent."""
    return distinct < positions, int(distinct - 1).bit_length()


def covering_sets(classes) -> list:
    """``classes[k][t]``: the class of table ``t`` in id set ``k``. Sets
    that between them hold every (table, class) pair, picked greedily:
    each the one with most of the pairs still missing."""
    want = {(t, c) for row in classes for t, c in enumerate(row)}
    picked = []
    while want:
        k = max(range(len(classes)), key=lambda k: len(
            want & set(enumerate(classes[k]))))
        picked.append(k)
        want -= set(enumerate(classes[k]))
    return picked


def sample_quota(rows, total: int) -> int:
    """The most rows to sample of one table so that tables of no more rows
    than that, taken whole, and that many of each larger one make ``total``
    (or every row there is)."""
    small, quota = sorted(rows), 0
    for i, r in enumerate(small):
        quota = (total - sum(small[:i])) // (len(small) - i)
        if r > quota:
            break
    return quota


def sample_rows(rng, rows: int, quota: int, perm, named):
    """Sorted distinct rows of one table to check: the whole table where it
    has no more than ``quota`` rows; else ``quota`` rows, a third of them
    the most frequent ranks (their repeat counts change from step to step:
    AdaGrad hides a count that never changes), up to a third rows no step
    names (a small table has few or none), the rest rows some step names."""
    if rows <= quota:
        return np.arange(rows, dtype=np.int32)
    often = perm[:quota // 3]
    free = np.setdiff1d(np.arange(rows), named)
    idle = rng.choice(free, min(quota // 3, len(free)), replace=False)
    rest = np.setdiff1d(named, often)
    some = rng.choice(rest, min(quota - len(often) - len(idle), len(rest)),
                      replace=False)
    return np.sort(np.concatenate([often, some, idle])).astype(np.int32)


class Runner:
    def __init__(self, cell, seed: int, rehearsal: bool):
        self.cell, self.seed = cell, seed
        self.cfg, self.mix = cell.config, cell.traffic
        self.up = False

    # -- set-up ------------------------------------------------------------

    def setup(self, workdir: str) -> None:
        import multiverso_tpu as mv
        from multiverso_tpu.tables import MatrixTableOption
        from multiverso_tpu.updaters.base import AddOption
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
        rng = np.random.default_rng(self.seed)

        # the traffic first: the sample of rows to check is drawn from it
        perms = [rng.permutation(r) for r in self.rows]
        self.pool = [[bag_ids(rng, r, bags, h, p) for r, h, p in zip(
            self.rows, self.hot, perms)] for _ in range(sets)]
        self.unique = [[len(np.unique(ids)) for ids in row]
                       for row in self.pool]
        quota = sample_quota(self.rows, int(work["sample_rows"]))
        self.sample = [sample_rows(
            rng, r, quota, perms[t],
            np.unique(np.concatenate([row[t] for row in self.pool])))
            for t, r in enumerate(self.rows)]

        self.tables, self.init = [], []
        for r, total, sample in zip(
                self.rows, published["num_embeddings_per_feature"],
                self.sample):
            full = rng.random((r, self.cols), dtype=np.float32)
            full = (2 * full - 1) * np.float32(1 / np.sqrt(total))
            self.init.append(full[sample].copy())
            self.tables.append(mv.MV_CreateTable(MatrixTableOption(
                num_rows=r, num_cols=self.cols, updater_type=cfg["updater"],
                initializer=lambda shape, full=full: full)))
            del full
        self.servers = [t.server() for t in self.tables]

        lr = self.lr

        @jax.jit
        def delta(rows, step, table):
            col = jnp.arange(rows.shape[1], dtype=jnp.int32)
            k = (step * 7 + col * 13 + table * 5) % 16
            pattern = (2 * k - 15).astype(jnp.float32) * jnp.float32(
                adagrad_rows.AMPLITUDE / 16)
            g = jnp.float32(adagrad_rows.SLOPE) * rows + pattern[None, :]
            return jnp.float32(lr) * g

        self._delta = delta
        # a scalar's copy to the device is a dispatch of its own: the
        # table numbers are placed once, the step once a step
        self.table_no = [jax.device_put(np.int32(t))
                         for t in range(len(self.rows))]
        self.positions = sum(len(ids) for ids in self.pool[0])
        self.history = []        # the id set of every step since creation
        cover = covering_sets([
            [distinct_class(len(ids), n) for ids, n in zip(row, distinct)]
            for row, distinct in zip(self.pool, self.unique)])
        self.warmup_sets = cover + cover[:1] * max(
            0, int(mix["warmup_steps_min"]) - len(cover))
        for k in self.warmup_sets:
            self._step(k)

    # -- the window --------------------------------------------------------

    def _step(self, k: int) -> None:
        step = jax.device_put(np.int32(len(self.history)))
        # the guarantee: nothing the device plane is handed or hands back
        # leaves the device (a program that copies a delta to the host to
        # combine its repeats raises here; the CPU of a rehearsal has no
        # such transfer to forbid)
        with trace.span("bench.step"), \
                jax.transfer_guard_device_to_host("disallow_explicit"):
            for srv, ids, number in zip(self.servers, self.pool[k],
                                        self.table_no):
                rows = srv.device_fetch_rows(ids)
                delta = self._delta(rows, step, number)
                del rows        # the fetched copy is not held over the apply
                srv.device_apply_rows(ids, delta, self.option)
                del delta
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
        median = float(np.median(lat_ms))
        slow = [i for i, ms in enumerate(lat_ms) if ms > 2 * median]
        return {"wall_s": sw.wall_s, "cpu_s": sw.cpu_s, "op_ms": lat_ms,
                "rows": len(done) * 2 * self.positions,
                "attempted": len(done), "failed": 0,
                "verbs": len(done) * 2 * len(self.tables),
                "row_verbs": self._row_verbs(done),
                "notes": [f"{len(done)} steps of {2 * self.positions} rows "
                          f"(fetched plus applied) in "
                          f"{2 * len(self.tables)} verbs over "
                          f"{len(self.tables)} tables, median step "
                          f"{median:.3f} ms; a step names "
                          f"{int(np.mean(np.sum(self.unique, axis=1)))} "
                          f"distinct rows in {self.positions} positions; "
                          f"the warm-up ran id sets {self.warmup_sets}",
                          # a closed loop stands still while its host does
                          f"{len(slow)} steps took over twice the median"
                          + (f": the slowest {max(lat_ms):.1f} ms, at step "
                             f"{int(np.argmax(lat_ms))} of the window; "
                             f"{sum(lat_ms[i] for i in slow):.1f} ms in all"
                             if slow else "")]}

    def _row_verbs(self, done) -> list:
        """What the window's verbs named, for the byte count of
        ``layer_metrics/row_plane_roofline.py``: one entry a table and verb
        with the positions and the distinct rows, summed over the steps."""
        used = np.bincount(done, minlength=len(self.pool))
        out = []
        for t in range(len(self.tables)):
            positions = int(sum(n * len(self.pool[k][t])
                                for k, n in enumerate(used)))
            unique = int(sum(n * self.unique[k][t]
                             for k, n in enumerate(used)))
            for verb in ("fetch", "apply"):
                # state 2: an apply reads and writes the row and its history
                out.append({"verb": verb, "positions": positions,
                            "unique": unique, "row_bytes": self.cols * 4,
                            "state": 2})
        return out

    # -- the check ---------------------------------------------------------

    def check(self) -> dict:
        tol = self.cell.workload["tolerance"]
        ok, notes, errs = True, [], []
        for t, (table, sample, init) in enumerate(zip(
                self.tables, self.sample, self.init)):
            per_set = np.stack([np.bincount(row[t], minlength=self.rows[t])[
                sample] for row in self.pool])
            want, _ = adagrad_rows.replay(
                init, [per_set[k] for k in self.history], t,
                learning_rate=self.lr, rho=self.rho)
            device = np.asarray(table.server().device_fetch_rows(sample))
            host = table.GetRows(sample)
            same = np.array_equal(device, host)
            idle = per_set.sum(axis=0) == 0
            kept = np.array_equal(host[idle], init[idle])
            err = np.abs(host.astype(np.float64) - want)
            errs.append(err.ravel())
            good = same and kept and float(err.max()) <= float(
                tol["worst_abs"])
            ok = ok and good
            notes.append(
                ("ok: " if good else "FAILED: ")
                + f"table {t} ({self.rows[t]} rows): {len(sample)} sampled "
                f"rows against the plain replay of {len(self.history)} "
                f"steps: worst entry {float(err.max()):.3e} off (limit "
                f"{tol['worst_abs']}), "
                f"{100 * float(np.mean(err <= float(tol['entry_abs']))):.4f}"
                f" % within {tol['entry_abs']}; device fetch and host Get "
                f"agree bit for bit ({same}); {int(idle.sum())} rows no "
                f"step named keep their initial values bit for bit ({kept})")
        errs = np.concatenate(errs)
        share = float(np.mean(errs <= float(tol["entry_abs"])))
        enough = share >= float(tol["entry_share"])
        notes.append(
            ("ok: " if enough else "FAILED: ")
            + f"all {len(self.tables)} tables: {len(errs) // self.cols} "
            f"sampled rows, worst entry {float(errs.max()):.3e} off, "
            f"{100 * share:.4f} % of entries within {tol['entry_abs']} (at "
            f"least {100 * float(tol['entry_share'])} %)")
        return {"correct": ok and enough, "notes": notes}

    def close(self) -> None:
        if self.up:
            self.mv.MV_ShutDown()
            self.up = False
