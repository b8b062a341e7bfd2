"""The WordEmbedding application as the reference runs it by default: the
block pipeline on the host plane (``-is_pipeline 1``: a block's rows come
through the server by ``MV_MultiGetAsync``, its deltas go back by
``AddFireForget``, the next block's rows are asked for while this one
trains). ``we_app``'s runner (the app's own ``prepare()`` / ``train()``, the
same window, the same row round) with what this mode needs:

* a settle that empties the engine: ``AddFireForget`` returns before the
  server has the delta, so the window's clock stops only after a blocking
  Get of one row of each table, which queues behind that table's Adds;
* the plain reference of the pipeline's semantics
  (``benchmark/reference/sgns_adagrad_pipeline.py``): block 0 and block 1 of
  a ``train()`` train on the tables as the call found them, block b >= 2 on
  the tables with the deltas of blocks 0..b-2 and no other. The
  correctness pass (the warm-up, from fresh tables) is held to it on the
  very pair stream it trained: the pair count exactly, the average loss,
  and a sample of the rows it named in all four tables. The sequential
  round (every block on the last one's deltas) is a tenth off in the loss
  at this cell's size and fails every limit.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.harness import trace
from benchmark.runners import we_app

#: (name, the communicator's table, the block's row set it is fetched by,
#: its place in the reference's tables)
TABLES = (("input", "input_table", "input_rows", 0),
          ("output", "output_table", "output_rows", 1),
          ("input_g2", "ie_g2_table", "input_rows", 2),
          ("output_g2", "eo_g2_table", "output_rows", 3))


def _laid_out(at: np.ndarray, spare: int) -> np.ndarray:
    """A block's row set in the reference's row space, laid out to the
    next multiple of an eighth of the power of two over its length on
    the ``spare`` row past that space: blocks of 970 to 1,027 thousand
    output rows are all 1,048,576 long, so the reference's programs
    have one shape whatever the block and the seed, and the machine's
    compile cache holds them. No batch names the spare row; its delta
    is zero."""
    step = 1 << max(0, int(len(at)).bit_length() - 3)
    pad = -len(at) % step
    return np.concatenate([at, np.full(pad, spare, at.dtype)])


def reference_pass(kept: list, vocab: int, dim: int, seed: int, lr: float,
                   prefetch_depth: int = 1, dtype="float32") -> dict:
    """The plain reference's pass over the kept blocks, from fresh tables,
    in the compact row space of the rows some block names and one spare
    row (another ``prefetch_depth`` or ``dtype`` is another deployment:
    the tests' and PERF.md's readings of what the limits refuse).
    -> ``loss`` (summed), ``pairs``, ``in_ids`` / ``out_ids`` (the row
    space) and ``tables``, the four of them as the pass left them."""
    from benchmark.reference import sgns_adagrad_pipeline as ref
    in_ids = np.unique(np.concatenate([b.input_rows for b in kept]))
    out_ids = np.unique(np.concatenate([b.output_rows for b in kept]))
    blocks = []
    for b in kept:
        st = b.stacked
        blocks.append({
            "input_rows": _laid_out(
                np.searchsorted(in_ids, b.input_rows), len(in_ids)),
            "output_rows": _laid_out(
                np.searchsorted(out_ids, b.output_rows), len(out_ids)),
            "batches": [{k: st[k][i] for k in st}
                        for i in range(st["inputs"].shape[0])]})
    zeros = lambda n: np.zeros((n + 1, dim), np.float32)  # noqa: E731
    ie = zeros(len(in_ids))
    ie[:-1] = ref.init_input(vocab, dim, seed)[in_ids]
    total, tables = ref.train_blocks(
        blocks, (ie, zeros(len(out_ids)), zeros(len(in_ids)),
                 zeros(len(out_ids))), lr, prefetch_depth=prefetch_depth,
        dtype=dtype)
    return {"loss": total, "pairs": sum(b.pair_count for b in kept),
            "in_ids": in_ids, "out_ids": out_ids,
            "tables": [t[:-1] for t in tables]}


def pipeline_verdicts(system: dict, reference: dict, limits: dict) -> list:
    """[(held, what)]. ``system``: ``loss`` (average a pair), ``pairs``,
    ``sample`` (a table's name -> (ids, rows as ``GetRows`` returned
    them)). ``reference``: ``reference_pass``'s result. ``limits``: the
    cell's file."""
    out = []
    out.append((system["pairs"] == reference["pairs"],
                f"pairs: system {system['pairs']}, the kept blocks "
                f"{reference['pairs']}: equal exactly (the reference "
                "trains the very pair stream)"))
    ref_loss = reference["loss"] / max(reference["pairs"], 1)
    gap = abs(system["loss"] - ref_loss) / ref_loss
    out.append((gap <= limits["loss_rel_tol"],
                f"reference average pair loss {ref_loss:.6f}, system "
                f"{system['loss']:.6f}: {gap:.3e} apart, tolerance "
                f"{limits['loss_rel_tol']:g}"))
    for name, _, rows_of, place in TABLES:
        ids, got = system["sample"][name]
        ref_ids = reference["in_ids" if rows_of == "input_rows"
                            else "out_ids"]
        at = np.searchsorted(ref_ids, ids)
        gaps = np.abs(got - reference["tables"][place][at])
        p99, worst = float(np.quantile(gaps, 0.99)), float(gaps.max())
        out.append((p99 <= limits["row_abs_tol"]
                    and worst <= limits["row_max_tol"],
                    f"{len(ids)} sampled {name} rows against the "
                    f"reference: 99 % of entries within {p99:.3e} "
                    f"(tolerance {limits['row_abs_tol']:g}), the worst "
                    f"{worst:.3e} (tolerance {limits['row_max_tol']:g})"))
    return out


class Runner(we_app.Runner):
    def setup(self, workdir: str) -> None:
        super().setup(workdir)
        if not (self.opt.is_pipeline and not self.opt.device_plane
                and not self.opt.device_pairs):
            raise RuntimeError("this runner drives the host plane's block "
                               "pipeline: -is_pipeline 1 -device_plane 0 "
                               "-device_pairs 0")

    def _settle(self) -> None:
        """Every delta sent so far is in the tables: a Get of a table
        queues behind the Adds of that table."""
        with trace.span("bench.settle"):
            one = np.zeros(1, np.int32)
            for table in self._tables():
                table.GetRows(one)
        super()._settle()

    def _sample_rows(self) -> None:
        """Rows of all four tables as the correctness pass left them, on
        a seeded sample of the rows it named (host-plane GetRows: the
        export path)."""
        n = self.cell.workload["sample_rows"]
        rng = np.random.default_rng(self.seed)
        self.sample = {}
        for name, table, rows_of, _ in TABLES:
            named = np.unique(np.concatenate(
                [getattr(b, rows_of) for b in self._kept]))
            ids = np.sort(rng.choice(named, min(n, len(named)),
                                     replace=False)).astype(np.int32)
            self.sample[name] = (ids, np.array(
                getattr(self.we.comm, table).GetRows(ids)))

    def check(self) -> dict:
        notes, ok = [], True

        def hold(cond: bool, what: str) -> None:
            nonlocal ok
            ok = ok and bool(cond)
            notes.append(("ok: " if cond else "FAILED: ") + what)

        opt = self.opt
        ceiling = (1 + opt.negative_num) * math.log(2.0)
        hold(math.isfinite(self.warm_loss) and self.warm_loss < ceiling,
             f"correctness pass: average pair loss {self.warm_loss:.5f} "
             f"over {self.warm_pairs} pairs in {len(self._kept)} blocks is "
             f"finite and below the zero-vector ceiling {ceiling:.4f}")
        reference = reference_pass(
            self._kept, self.cfg["vocabulary"], opt.embedding_size,
            opt.seed, opt.init_learning_rate)
        system = {"loss": self.warm_loss, "pairs": self.warm_pairs,
                  "sample": self.sample}
        for held, what in pipeline_verdicts(system, reference,
                                            self.cell.workload):
            hold(held, what)
        hold(*self._oracle_round())
        return {"correct": ok, "notes": notes}
