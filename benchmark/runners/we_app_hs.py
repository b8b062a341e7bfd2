"""The WordEmbedding application's other objective, CBOW with hierarchical
softmax (``-cbow 1 -hs 1 -negative 0``): ``we_app``'s runner (the app's own
``prepare()`` / ``train()``, the same window, the same row round) with the
plain reference of this objective and what it allows ``correct`` to hold.

Nothing of an HS pass is random but the shrunk window, so after the
correctness pass (the warm-up, from fresh tables) and the reference's pass
over the same blocks on windows it draws itself
(``benchmark/reference/cbow_hs_adagrad.py``, its own tree from the counts):

* the count of examples equals the reference's exactly (a word with a
  neighbour in its sentence is an example under every window, since a
  window is at least 1) and the average loss an example is within
  ``loss_rel_tol``;
* exact, from the reference's tree: no output row outside the union of the
  pass's tokens' paths has moved or has a non-zero accumulator (row
  ``V - 1``, which is no node, among them), a row has moved exactly where
  its accumulator is non-zero, and at least ``moved_share_min`` of the rows
  inside the union have;
* the root's accumulator row sums to within ``root_rel_tol`` of the
  reference's (every lane-batch sums 8,192 gradients into that one row, so
  unsummed repeats show here first; the row itself is a walk that another
  draw of windows turns elsewhere, so it is not compared);
* the tables the pass left, read back for every row it named, lose on the
  first block's examples, under the reference's tree and windows and at
  rate 0, within ``eval_rel_tol`` of what the reference's own tables lose
  there (labels ``c`` for ``1 - c`` train the mirror image: the same loss,
  the same input rows, every output row turned round; only this reading
  shows it);
* ``idle_rows`` words no token names keep ``init_input``'s rows and zero
  accumulator rows bit for bit; the device's fetch and the host's
  ``GetRows`` of every sample agree bit for bit.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.runners import we_app
from benchmark.runners.we_app_sharded import idle_sample, init_rows

#: rows of the output table whose moved / fed flags one device program
#: reduces at a time (128 MB of rows and as much of accumulators)
FLAG_ROWS = 1 << 18


def rel_distance(a: np.ndarray, b: np.ndarray) -> float:
    """|a - b| / |b| of two vectors, in float64."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


def hs_verdicts(system: dict, reference: dict, limits: dict) -> list:
    """[(held, what)]. ``system``: ``examples``, ``loss`` (average an
    example), ``out_moved`` / ``out_fed`` (a flag an output row: any entry
    of the row / of its accumulator row is not zero), ``root`` and ``idle``
    (samples: ``ids``, ``rows``, ``g2`` as the device fetched them,
    ``host_rows`` / ``host_g2`` as ``GetRows`` returned them, ``idle`` with
    ``init``), ``eval_loss``. ``reference``:
    ``reference.cbow_hs_adagrad.train_pass``'s result with ``eval_loss``
    (``loss_at_rate_0`` of both sides' tables). ``limits``: the cell's
    file."""
    out = []
    ref_loss = reference["loss"] / max(reference["examples"], 1)
    out.append((system["examples"] == reference["examples"],
                f"examples: system {system['examples']}, reference "
                f"{reference['examples']}: equal exactly (a word with a "
                "neighbour is an example under every window)"))
    gap = abs(system["loss"] - ref_loss) / ref_loss
    out.append((gap <= limits["loss_rel_tol"],
                f"reference average loss an example {ref_loss:.6f}, system "
                f"{system['loss']:.6f}: {gap:.3e} apart, tolerance "
                f"{limits['loss_rel_tol']:g}"))
    moved, fed = system["out_moved"], system["out_fed"]
    inside = np.zeros(len(moved), bool)
    inside[reference["out_ids"]] = True
    stray = int((moved & ~inside).sum()), int((fed & ~inside).sum())
    out.append((not any(stray) and not inside[-1],
                f"{int((~inside).sum())} output rows outside the union of "
                f"the pass's tokens' paths (row {len(moved) - 1}, no node, "
                f"among them): {stray[0]} moved, {stray[1]} with a non-zero "
                "accumulator"))
    share = float(moved[inside].mean()) if inside.any() else 0.0
    out.append((share >= limits["moved_share_min"]
                and bool((moved == fed).all()),
                f"{int(inside.sum())} output rows on those paths: "
                f"{100 * share:.4f} % moved (at least "
                f"{100 * limits['moved_share_min']:g} %); a row moved "
                f"exactly where its accumulator is non-zero "
                f"({int((moved != fed).sum())} rows otherwise)"))
    root = system["root"]
    at = int(np.searchsorted(reference["out_ids"], root["ids"][0]))
    is_root = (root["ids"][0] == len(moved) - 2
               and reference["out_ids"][at] == root["ids"][0])
    fed_sum, ref_sum = (float(np.sum(g2, dtype=np.float64))
                        for g2 in (root["g2"][0], reference["eo_g2"][at]))
    gap = abs(fed_sum - ref_sum) / ref_sum
    out.append((is_root and gap <= limits["root_rel_tol"],
                f"the root (output row {int(root['ids'][0])}): its "
                f"accumulator row sums to {fed_sum:.6e}, the reference's to "
                f"{ref_sum:.6e}: {gap:.3e} apart, tolerance "
                f"{limits['root_rel_tol']:g} (entry by entry the two rows "
                f"are {rel_distance(root['g2'][0], reference['eo_g2'][at]):.3e}"
                " apart, relative)"))
    gap = (abs(system["eval_loss"] - reference["eval_loss"])
           / reference["eval_loss"])
    out.append((gap <= limits["eval_rel_tol"],
                f"the first block's examples at rate 0: the system's tables "
                f"lose {system['eval_loss']:.6f} an example, the "
                f"reference's {reference['eval_loss']:.6f}: {gap:.3e} apart, "
                f"tolerance {limits['eval_rel_tol']:g}"))
    idle = system["idle"]
    kept = (idle["rows"] == idle["init"]).all(axis=1)
    zero = ~idle["g2"].any(axis=1)
    out.append((bool(kept.all() and zero.all())
                and not np.isin(idle["ids"], reference["in_ids"]).any(),
                f"{len(kept)} words no token names keep their initial "
                f"input rows and zero accumulator rows bit for bit "
                f"({int((~kept).sum())} rows and {int((~zero).sum())} "
                "accumulator rows do not)"))
    same = all(np.array_equal(s[a], s[b]) for s in (root, idle)
               for a, b in (("rows", "host_rows"), ("g2", "host_g2")))
    out.append((same, "device_fetch_rows and the host's GetRows of both "
                "samples, rows and accumulator rows, agree bit for bit"))
    return out


def loss_at_rate_0(tables: dict, blocks, counts, opt, tree, seed) -> float:
    """What ``tables`` (``in_ids`` with ``ie``, ``out_ids`` with ``eo``)
    lose an example on ``blocks`` under the reference's tree, on windows
    drawn from ``seed``: a reference pass at rate 0, which changes no
    row."""
    from benchmark.reference import cbow_hs_adagrad
    got = cbow_hs_adagrad.train_pass(
        blocks, counts, opt.embedding_size, opt.seed, 0.0, opt.window_size,
        opt.pair_batch_size, np.random.default_rng(seed), tree=tree,
        start=tables)
    return got["loss"] / max(got["examples"], 1)


class Runner(we_app.Runner):
    def __init__(self, cell, seed: int, rehearsal: bool):
        super().__init__(cell, seed, rehearsal)
        self.rehearsal = rehearsal

    def setup(self, workdir: str) -> None:
        from multiverso_tpu.models.wordembedding.huffman import HuffmanEncoder
        if not hasattr(HuffmanEncoder(), "lengths"):
            # before a minute of set-up, not after it
            raise RuntimeError(
                "this runner reads the Huffman encoder's arrays (points, "
                "codes, lengths), which the program has had since PR 39")
        if self.rehearsal:
            # a rehearsal's tables are kilobytes: under the program's
            # threshold they would take its dense step, which the chip run
            # never sees
            from multiverso_tpu.models.wordembedding import device_pairs
            device_pairs._SPARSE_BYTES = 0
        super().setup(workdir)

    def _fetch(self, ids: np.ndarray, tables) -> dict:
        got = {"ids": ids}
        for key, table in zip(("rows", "g2"), tables):
            got[key] = np.asarray(table.server().device_fetch_rows(ids))
            got["host_" + key] = np.array(table.GetRows(ids))
        return got

    def _flags(self, table) -> np.ndarray:
        """A flag an output row: some entry of it is not zero. Reduced on
        the device, ``FLAG_ROWS`` rows a program, so that what comes back
        is a byte a row."""
        import jax.numpy as jnp
        vocab, srv = self.cfg["vocabulary"], table.server()
        flags = [np.asarray(jnp.any(srv.device_fetch_rows(np.arange(
            at, min(at + FLAG_ROWS, vocab), dtype=np.int32)) != 0, axis=1))
            for at in range(0, vocab, FLAG_ROWS)]
        return np.concatenate(flags)

    def _sample_rows(self) -> None:
        """What the correctness pass left, read before the window trains
        on: the output table's flags, the root, the idle words."""
        self.sample = None
        comm, vocab = self.we.comm, self.cfg["vocabulary"]
        self.out_moved = self._flags(comm.output_table)
        self.out_fed = self._flags(comm.eo_g2_table)
        self.root = self._fetch(np.array([vocab - 2], np.int32),
                                (comm.output_table, comm.eo_g2_table))
        tokens = np.unique(np.concatenate(
            [b.tokens for b in self._kept])).astype(np.int32)
        # every row the pass named, as it left them. Which output rows
        # those are is asked of the program's own tree here (the
        # reference's is not built yet); a row the reference's tree names
        # and this set lacks reads as fresh in check()
        enc = self.we.huffman
        on_path = (np.arange(enc.points.shape[1])[None, :]
                   < enc.lengths[tokens][:, None])
        nodes = np.unique(enc.points[tokens][on_path]).astype(np.int32)
        srv_in, srv_out = (t.server() for t in (comm.input_table,
                                                comm.output_table))
        self.trained = {
            "in_ids": tokens, "out_ids": nodes,
            "ie": np.asarray(srv_in.device_fetch_rows(tokens)),
            "eo": np.asarray(srv_out.device_fetch_rows(nodes))}
        self.idle = self._fetch(
            idle_sample(np.random.default_rng(self.seed + 3), vocab, vocab,
                        tokens, self.cell.workload["idle_rows"]),
            (comm.input_table, comm.ie_g2_table))

    def check(self) -> dict:
        from benchmark.reference import cbow_hs_adagrad
        notes, ok = [], True

        def hold(cond: bool, what: str) -> None:
            nonlocal ok
            ok = ok and bool(cond)
            notes.append(("ok: " if cond else "FAILED: ") + what)

        opt = self.opt
        reference = cbow_hs_adagrad.train_pass(
            [(b.tokens, b.token_sent) for b in self._kept],
            self.we.dictionary.counts(), opt.embedding_size, opt.seed,
            opt.init_learning_rate, opt.window_size, opt.pair_batch_size,
            np.random.default_rng(self.seed + 1))
        # every output row starts at zero: log 2 a node of a centre's path
        tokens = np.concatenate([b.tokens for b in self._kept])
        ceiling = math.log(2.0) * float(reference["lengths"][
            np.searchsorted(reference["in_ids"], tokens)].mean())
        hold(math.isfinite(self.warm_loss) and self.warm_loss < ceiling,
             f"correctness pass: average loss an example "
             f"{self.warm_loss:.5f} over {self.warm_pairs} examples is "
             f"finite and below the zero-vector ceiling {ceiling:.4f}")
        self.idle["init"] = init_rows(self.idle["ids"], opt.embedding_size,
                                      opt.seed)
        first, counts = [(self._kept[0].tokens, self._kept[0].token_sent)], \
            self.we.dictionary.counts()
        losses = [loss_at_rate_0(tables, first, counts, opt,
                                 reference["tree"], self.seed + 4)
                  for tables in (self.trained, reference)]
        reference["eval_loss"] = losses[1]
        system = {"examples": self.warm_pairs, "loss": self.warm_loss,
                  "eval_loss": losses[0],
                  "out_moved": self.out_moved, "out_fed": self.out_fed,
                  "root": self.root, "idle": self.idle}
        for held, what in hs_verdicts(system, reference, self.cell.workload):
            hold(held, what)
        hold(*self._oracle_round())
        return {"correct": ok, "notes": notes}
