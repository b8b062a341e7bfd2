"""Drives the WordEmbedding application through its own entry points:
``Option.parse_args``, ``DistributedWordEmbedding.prepare()`` and
``train()``, what ``models.wordembedding.distributed.main`` calls.

``train()`` has no stop hook, so a window of ``--seconds`` is one
``train()`` call (one loader, one drain, as in a real job) whose ``-epoch``
count is what fills ``--seconds`` at the mix's nominal rate: the same work
in every run. The warm-up epoch starts from fresh tables and is also the
correctness epoch: its blocks are kept, and after the window the plain
reference trains the same epoch.
"""

from __future__ import annotations

import math
import os
import time

import numpy as np

from benchmark.harness import trace, traffic
from benchmark.harness.run_record import Stopwatch

ORACLE_ROWS = 1000


def _flags(options: dict) -> list:
    out = []
    for key, value in options.items():
        out += [f"-{key}", str(value)]
    return out


class _Laps:
    """Where set-up time goes, for an earlier line of the run."""

    def __init__(self):
        self._at, self._laps = time.perf_counter(), []

    def __call__(self, what: str) -> None:
        now = time.perf_counter()
        self._laps.append(f"{what} {now - self._at:.2f} s")
        self._at = now

    def told(self) -> str:
        return "; ".join(self._laps)


class Runner:
    def __init__(self, cell, seed: int, rehearsal: bool):
        self.cell, self.seed = cell, seed
        self.cfg, self.mix = cell.config, cell.traffic
        self.we = None

    # -- set-up -------------------------------------------------------------

    def setup(self, workdir: str) -> None:
        from multiverso_tpu.models.wordembedding.distributed import (
            DistributedWordEmbedding)
        from multiverso_tpu.models.wordembedding.option import Option
        cfg, corpus = self.cfg, self.cfg["corpus"]
        lap = _Laps()
        vocab_path, corpus_path, _ = traffic.write_vocab_and_corpus(
            workdir, cfg["vocabulary"], corpus["words"],
            corpus["topic_words"], corpus["sentence_words"],
            corpus["nominal_words"], self.seed)
        self.opt = Option.parse_args(
            ["-train_file", corpus_path, "-read_vocab", vocab_path,
             "-output", os.path.join(workdir, "vectors.bin"),
             "-seed", str(self.seed)]
            + _flags({**cfg["options"], **self.mix["options"]}))
        lap("vocabulary and corpus files")
        self.we = DistributedWordEmbedding(self.opt)
        self.we.prepare()
        lap("prepare(): dictionary, sampler, world, tables")
        if self.we.dictionary.Size() != cfg["vocabulary"]:
            raise RuntimeError(f"vocabulary is {self.we.dictionary.Size()}, "
                               f"not {cfg['vocabulary']}")
        self._wrap_train_block()
        # the warm-up and correctness epoch, from fresh tables
        self._keep, self._kept, self._starts = True, [], []
        self.opt.epoch = 1
        self.warm_loss = self.we.train()
        self._settle()
        self._keep = False
        self.warm_pairs = int(self.we.total_pairs)
        self.words_per_epoch = self._words
        lap("warm-up and correctness epoch")
        self._sample_rows()
        lap("sampled rows")
        print("set-up laps: " + lap.told(), flush=True)

    def _wrap_train_block(self) -> None:
        """The benchmark's own span around the app's block step (the app
        loop has none), and the record the reference trains from."""
        inner = self.we._train_block
        self._words = 0

        def train_block(block, step):
            self._starts.append(time.perf_counter())
            self._words += block.word_count
            if self._keep:
                self._kept.append(block)
            with trace.span("bench.block"):
                return inner(block, step)

        self.we._train_block = train_block

    def _tables(self):
        c = self.we.comm
        return [c.input_table, c.output_table, c.ie_g2_table, c.eo_g2_table]

    def _settle(self) -> None:
        """Every update dispatched so far is in the tables."""
        import jax
        jax.block_until_ready([t.server().state["data"]
                               for t in self._tables()])

    def _sample_rows(self) -> None:
        """Rows of the input and output tables as the correctness epoch
        left them, on a seeded sample of the rows it named (host-plane
        GetRows: the export path)."""
        self.sample = None
        n = self.cell.workload.get("sample_rows", 0)
        if not n or self._kept[0].stacked is None:
            return
        rng = np.random.default_rng(self.seed)
        comm = self.we.comm
        self.sample = {}
        for name, table, attr in (("input", comm.input_table, "input_rows"),
                                  ("output", comm.output_table,
                                   "output_rows")):
            named = np.unique(np.concatenate(
                [getattr(b, attr) for b in self._kept]))
            ids = np.sort(rng.choice(named, min(n, len(named)),
                                     replace=False)).astype(np.int32)
            self.sample[name] = (ids, np.array(table.GetRows(ids)))

    # -- the window ---------------------------------------------------------

    def window(self, seconds: float, traced: bool) -> dict:
        epochs = (int(self.mix["traced_epochs"]) if traced else max(1, round(
            seconds * self.mix["nominal_items_per_s"]
            / self.words_per_epoch)))
        self.opt.epoch = epochs
        self._starts, self._words = [], 0
        called = time.perf_counter()
        with Stopwatch() as sw:
            loss = self.we.train()
            self._settle()
        blocks = len(self._starts)
        return {"wall_s": sw.wall_s, "cpu_s": sw.cpu_s,
                "items": self._words, "attempted": blocks,
                "failed": 0 if math.isfinite(loss) else blocks,
                "notes": [
                    f"{epochs} epoch(s) of {self.words_per_epoch} "
                    f"{self.cell.workload['item']} in one train() call, "
                    f"first block {self._starts[0] - called:.3f} s after "
                    f"the call, {blocks} blocks, {self.we.total_pairs} "
                    f"pairs, average pair loss {loss:.4f}"]}

    # -- correctness --------------------------------------------------------

    def check(self) -> dict:
        notes, ok = [], True

        def hold(cond: bool, what: str) -> None:
            nonlocal ok
            ok = ok and bool(cond)
            notes.append(("ok: " if cond else "FAILED: ") + what)

        tol = self.cell.workload
        ceiling = (1 + self.opt.negative_num) * math.log(2.0)
        hold(math.isfinite(self.warm_loss) and self.warm_loss < ceiling,
             f"correctness epoch: average pair loss {self.warm_loss:.5f} "
             f"over {self.warm_pairs} pairs is finite and below the "
             f"zero-vector ceiling {ceiling:.4f}")
        ref_loss, ref_pairs, rows = self._reference_epoch()
        rel = abs(self.warm_loss - ref_loss) / ref_loss
        hold(rel <= tol["loss_rel_tol"],
             f"reference average pair loss {ref_loss:.5f}, system "
             f"{self.warm_loss:.5f}: {rel:.2e} apart, tolerance "
             f"{tol['loss_rel_tol']:g}")
        pair_gap = abs(self.warm_pairs - ref_pairs) / ref_pairs
        hold(pair_gap <= tol["pairs_rel_tol"],
             f"reference pairs {ref_pairs}, system {self.warm_pairs}: "
             f"{pair_gap:.2e} apart, tolerance {tol['pairs_rel_tol']:g}")
        if self.sample is not None:
            for name, (ids, got) in self.sample.items():
                ref_ids, ref_rows = rows[name]
                at = np.minimum(np.searchsorted(ref_ids, ids),
                                len(ref_ids) - 1)
                named = ref_ids[at] == ids      # some lane names the row
                gaps = np.abs(got[named] - ref_rows[at[named]])
                p99, worst = float(np.quantile(gaps, 0.99)), float(gaps.max())
                hold(p99 <= tol["row_abs_tol"]
                     and worst <= tol["row_max_tol"],
                     f"{int(named.sum())} sampled {name} rows against the "
                     f"reference: 99 % of entries within {p99:.3e} "
                     f"(tolerance {tol['row_abs_tol']:g}), the worst "
                     f"{worst:.3e} (tolerance {tol['row_max_tol']:g})")
                if name == "output" and not named.all():
                    hold(not got[~named].any(),
                         f"{int((~named).sum())} sampled output rows that "
                         "no lane names are still zero")
        hold(*self._oracle_round())
        return {"correct": ok, "notes": notes}

    def _reference_epoch(self):
        from benchmark.reference import sgns_adagrad, sgns_pairs
        opt = self.opt
        batches, pairs = [], 0
        if self._kept[0].stacked is not None:
            for block in self._kept:
                st = block.stacked
                pairs += block.pair_count
                for i in range(st["inputs"].shape[0]):
                    batches.append({
                        "inputs": block.input_rows[st["inputs"][i]],
                        "input_mask": st["input_mask"][i],
                        "outputs": block.output_rows[st["outputs"][i]],
                        "labels": st["labels"][i],
                        "output_mask": st["output_mask"][i]})
        else:
            rng = np.random.default_rng(self.seed + 1)
            cdf = sgns_pairs.unigram_cdf(self.we.dictionary.counts())
            for block in self._kept:
                got, live = sgns_pairs.lane_batches(
                    block.tokens, block.token_sent, opt.window_size,
                    opt.negative_num, cdf, opt.pair_batch_size, rng)
                batches += got
                pairs += live
        total, in_ids, in_rows, out_ids, out_rows = sgns_adagrad.train_epoch(
            batches, self.cfg["vocabulary"], opt.embedding_size, opt.seed,
            opt.init_learning_rate)
        return (total / max(pairs, 1), pairs,
                {"input": (in_ids, in_rows), "output": (out_ids, out_rows)})

    def _oracle_round(self):
        """``chip_smoke.py``'s row round on the input table: fetch, apply,
        host GetRows on ORACLE_ROWS random rows against numpy, bit for bit
        (the ``+=`` updater is one float32 add), and as many other rows
        untouched."""
        rng = np.random.default_rng(self.seed + 2)
        table = self.we.comm.input_table
        srv = table.server()
        vocab, dim = self.cfg["vocabulary"], self.opt.embedding_size
        both = rng.choice(vocab, 2 * min(ORACLE_ROWS, vocab // 2),
                          replace=False).astype(np.int32)
        ids, others = both[: len(both) // 2], both[len(both) // 2:]
        before = np.asarray(srv.device_fetch_rows(ids))
        others_before = np.array(table.GetRows(others))
        delta = rng.standard_normal((len(ids), dim)).astype(np.float32)
        srv.device_apply_rows(ids, delta)
        exact = (np.array_equal(table.GetRows(ids), before + delta)
                 and np.array_equal(table.GetRows(others), others_before))
        return exact, (f"device_fetch_rows / device_apply_rows / GetRows on "
                       f"{len(ids)} rows match numpy bit for bit, "
                       f"{len(others)} other rows untouched")

    def close(self) -> None:
        if self.we is not None:
            self.we.close()
