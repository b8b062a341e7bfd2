"""The WordEmbedding application over tables that no chip holds whole:
``we_app``'s runner (the app's own ``prepare()`` / ``train()``, the same
window, the same reference epoch and row round) with what block sharding
adds to ``correct``.

After the correctness epoch, shard by shard of the input table and of its
AdaGrad accumulator:

* the rows of the words the epoch's tokens name have moved off their
  initial values and their accumulator rows are positive, on every shard
  (a shard whose updates were dropped shows none); a row moves exactly
  when its accumulator does;
* a sample of words no token names, a quarter on each shard, keeps its
  initial input rows (``reference.sgns_adagrad.init_input``'s) and zero
  accumulator rows bit for bit (tables kept in a lower precision do not);
* the device's fetch and the host's ``GetRows`` of both samples agree bit
  for bit.

An input row's gradient is ``err . out_rows``, and the output table starts
at zero: a word all of whose lanes met only untouched output rows keeps its
row, exactly. Such words are rare and not none, so the first check holds a
share (``moved_share_min`` in the cell's file), not every word.
"""

from __future__ import annotations

import numpy as np

from benchmark.runners import we_app


def init_rows(ids: np.ndarray, dim: int, seed: int) -> np.ndarray:
    """``reference.sgns_adagrad.init_input(vocab, dim, seed)[ids]`` without
    the table (4.3 GB and half a minute at 8.4 M words): numpy's PCG64
    makes two float32 draws of one 64-bit step, so row ``r`` of an even
    ``dim`` starts ``r * dim / 2`` steps in.
    ``benchmark/tests/test_we_pairs_4c.py`` holds it to ``init_input``."""
    if dim % 2:
        raise ValueError("an odd width does not start a row on a step")
    out = np.empty((len(ids), dim), np.float32)
    at, rng = 0, np.random.default_rng(seed)
    for i, r in enumerate(ids.tolist()):    # ascending ids: forward only
        rng.bit_generator.advance(r * dim // 2 - at)
        out[i] = rng.random(dim, np.float32)
        at = (r + 1) * dim // 2
    return ((out - 0.5) / dim).astype(np.float32)


def idle_sample(rng, vocab: int, block_rows: int, named: np.ndarray,
                n: int) -> np.ndarray:
    """``n`` words that ``named`` (sorted, distinct) leaves out, as near a
    quarter a shard as the shards' idle words allow, ascending."""
    shards = -(-vocab // block_rows)
    out = []
    for k in range(shards):
        lo, hi = k * block_rows, min((k + 1) * block_rows, vocab)
        taken = named[(named >= lo) & (named < hi)]
        want = min(n // shards, hi - lo - len(taken))
        # draw more than wanted, drop the named, keep the first ``want``
        draw = rng.choice(hi - lo, min(hi - lo, 2 * want + len(taken)),
                          replace=False) + lo
        out.append(draw[~np.isin(draw, taken)][:want])
    return np.sort(np.concatenate(out)).astype(np.int32)


def shard_verdicts(named: dict, idle: dict, block_rows: int, shards: int,
                   moved_share_min: float) -> list:
    """[(held, what)] from two samples of the input table. A sample is a
    dict of ``ids`` (ascending), ``init`` (their initial rows), ``rows`` and
    ``g2`` as the device fetched them and ``host_rows`` / ``host_g2`` as
    ``GetRows`` returned them."""
    out = []
    moved = (named["rows"] != named["init"]).any(axis=1)
    fed = (named["g2"] > 0).any(axis=1)
    owner = named["ids"] // block_rows
    shares = [float(moved[owner == k].mean()) if (owner == k).any() else 0.0
              for k in range(shards)]
    out.append((min(shares) >= moved_share_min,
                "input rows of the epoch's distinct tokens that moved off "
                "their initial values, by shard: "
                + ", ".join(f"{int((owner == k).sum())} words {100 * s:.3f} %"
                            for k, s in enumerate(shares))
                + f" (each at least {100 * moved_share_min:g} %)"))
    out.append((bool((moved == fed).all() and (named["g2"] >= 0).all()),
                f"a token's input row moved exactly where its accumulator "
                f"row is positive ({int((moved != fed).sum())} of "
                f"{len(moved)} words otherwise)"))
    owner = idle["ids"] // block_rows
    kept = (idle["rows"] == idle["init"]).all(axis=1)
    zero = ~idle["g2"].any(axis=1)
    out.append((bool(kept.all() and zero.all()
                     and len(set(owner.tolist())) == shards),
                f"{len(kept)} words no token names ("
                + ", ".join(str(int((owner == k).sum()))
                            for k in range(shards))
                + f" by shard) keep their initial input rows and zero "
                f"accumulator rows bit for bit ({int((~kept).sum())} rows "
                f"and {int((~zero).sum())} accumulator rows do not)"))
    same = all(np.array_equal(s[a], s[b]) for s in (named, idle)
               for a, b in (("rows", "host_rows"), ("g2", "host_g2")))
    out.append((same, "device_fetch_rows and the host's GetRows of both "
                "samples, rows and accumulator rows, agree bit for bit"))
    return out


class Runner(we_app.Runner):
    def __init__(self, cell, seed: int, rehearsal: bool):
        super().__init__(cell, seed, rehearsal)
        self.rehearsal = rehearsal

    def setup(self, workdir: str) -> None:
        if self.rehearsal:
            # a rehearsal's tables are kilobytes: under the program's
            # threshold they would take its dense step, which the chip run
            # never sees
            from multiverso_tpu.models.wordembedding import device_pairs
            device_pairs._SPARSE_BYTES = 0
        super().setup(workdir)

    def _fetch(self, ids: np.ndarray) -> dict:
        comm = self.we.comm
        got = {"ids": ids}
        for key, table in (("rows", comm.input_table),
                           ("g2", comm.ie_g2_table)):
            got[key] = np.asarray(table.server().device_fetch_rows(ids))
            got["host_" + key] = np.array(table.GetRows(ids))
        return got

    def _sample_rows(self) -> None:
        """Besides ``we_app``'s: the input rows and accumulator rows of
        every distinct token of the correctness epoch and of words it does
        not name, as that epoch left them."""
        super()._sample_rows()
        srv = self.we.comm.input_table.server()
        self.block_rows, self.shards = srv.block_rows, srv.num_servers
        tokens = np.unique(np.concatenate(
            [b.tokens for b in self._kept])).astype(np.int32)
        rng = np.random.default_rng(self.seed + 3)
        self.named = self._fetch(tokens)
        self.idle = self._fetch(idle_sample(
            rng, self.cfg["vocabulary"], self.block_rows, tokens,
            self.cell.workload["idle_rows"]))

    def window(self, seconds: float, traced: bool) -> dict:
        w = super().window(seconds, traced)
        import jax
        used = [(d.memory_stats() or {}).get("bytes_in_use", 0)
                for d in jax.devices()]
        w["notes"].append("bytes in use after the window, by chip: "
                          + ", ".join(map(str, used)))
        return w

    def check(self) -> dict:
        verdict = super().check()
        dim, seed = self.opt.embedding_size, self.opt.seed
        for sample in (self.named, self.idle):
            sample["init"] = init_rows(sample["ids"], dim, seed)
        for held, what in shard_verdicts(
                self.named, self.idle, self.block_rows, self.shards,
                self.cell.workload["moved_share_min"]):
            verdict["correct"] = verdict["correct"] and bool(held)
            verdict["notes"].append(("ok: " if held else "FAILED: ") + what)
        return verdict
