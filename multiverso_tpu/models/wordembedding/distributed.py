"""DistributedWordEmbedding driver.

Behavioral equivalent of reference
Applications/WordEmbedding/src/distributed_wordembedding.h/.cpp: Run ->
Train -> per-block loop (loader thread fills a BlockQueue; each block:
fetch params for the block vocab, train all pairs, push deltas; optional
pipeline prefetching the NEXT block's params while training the current —
distributed_wordembedding.cpp:147-252), words/sec logging (trainer.cpp:45-49),
and rank-0 embedding export in word2vec text/binary format (:263-306).
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np

import multiverso_tpu as mv
from multiverso_tpu.models.wordembedding.communicator import Communicator
from multiverso_tpu.models.wordembedding.data import (BlockQueue, DataBlock,
                                                      PairGenerator,
                                                      start_loader)
from multiverso_tpu.models.wordembedding.dictionary import Dictionary
from multiverso_tpu.models.wordembedding.huffman import HuffmanEncoder
from multiverso_tpu.models.wordembedding.model import (decayed_lr,
                                                       make_train_step)
from multiverso_tpu.models.wordembedding.option import Option
from multiverso_tpu.models.wordembedding.sampler import Sampler
from multiverso_tpu.telemetry import metrics as tmetrics
from multiverso_tpu.telemetry import startup
from multiverso_tpu.telemetry import trace as ttrace
from multiverso_tpu.utils import compile_cache
from multiverso_tpu.utils.log import Log
from multiverso_tpu.utils.timer import Timer


def _steps_to_run(state, pair_count: int, batch: int, nb: int) -> int:
    """The trip count a block round's program is handed: the batches that
    hold a pair, ``ceil(pair_count / batch)`` of the ``nb`` laid out
    (``PairGenerator._finalize_block`` fills the pair axis in order; a last
    batch with one live lane counts). A state laid over devices of more
    than one process would make the program a collective one, which every
    process must run alike whatever its own block holds: there ``nb``
    (both planes hand over a state of this process's own devices today:
    a multi-process fetch cuts its rows out of an addressable copy)."""
    if not all(rows.is_fully_addressable
               for rows in state if rows is not None):
        return nb
    return -(-pair_count // batch)


class DistributedWordEmbedding:
    def __init__(self, option: Option):
        self.opt = option
        self.dictionary: Optional[Dictionary] = None
        self.huffman: Optional[HuffmanEncoder] = None
        self.sampler: Optional[Sampler] = None
        self.comm: Optional[Communicator] = None
        from multiverso_tpu.utils.world import WorldOwner
        self._world = WorldOwner()
        self.total_loss = 0.0
        self.total_pairs = 0
        self._blocks_done = 0   # index of the next block, per train()
        # what depends on prepare()'s products alone is made once per
        # trainer, not per train() call: the jit'd pair-batch step and the
        # block programs scanning a step, by (use_adagrad, touched rows)
        self._step = None
        self._block_scan_cache = {}

    # -- setup --------------------------------------------------------------

    def prepare(self) -> None:
        """Dictionary, sampler, world, tables, trainer. No trace covers
        set-up, so each part's seconds go to a ``we.prepare.*_s`` gauge:
        the host's parts as phases of the start (``telemetry/startup.py``),
        the laps from the world on once the world is up."""
        opt = self.opt
        with startup.phase("we.prepare.dictionary"):
            stop = set()
            if opt.stopwords and opt.sw_file:
                with open(opt.sw_file, encoding="utf-8") as f:
                    stop = set(f.read().split())
            if opt.read_vocab_file:
                self.dictionary = Dictionary.load_vocab(opt.read_vocab_file,
                                                        stop)
            else:
                self.dictionary = Dictionary(stop)
                self.dictionary.build_from_corpus(opt.train_file)
            self.dictionary.RemoveWordsLessThan(max(opt.min_count, 1))
            if self.dictionary.Size() == 0:
                raise ValueError("empty vocabulary after min_count pruning")
            # the dictionary is final: build the loader's tokenizer here,
            # once, and not at the start of every pass. Part of the
            # dictionary's phase; its own seconds go to
            # we.prepare.tokenizer_s besides
            with startup.phase("we.prepare.tokenizer"):
                self.dictionary.tokenizer()
            if opt.total_words <= 0:
                opt.total_words = self.dictionary.WordCount()
            counts = self.dictionary.counts()
        with startup.phase("we.prepare.sampler"):
            self.sampler = Sampler(counts, seed=opt.seed)
        if opt.hs:
            with startup.phase("we.prepare.huffman"):
                self.huffman = HuffmanEncoder()
                self.huffman.BuildFromTermFrequency(counts)
        laps, at = {}, time.perf_counter()

        def lap(part: str) -> None:
            nonlocal at
            now = time.perf_counter()
            laps[part], at = now - at, now

        self._world.init_if_needed()
        lap("world")
        # exception-safe: anything raising after MV_Init (table creation,
        # trainer CHECKs) must not strand a started Zoo the caller can
        # never shut down
        with self._world.guard("wordembedding.prepare"):
            self.comm = Communicator(opt, self.dictionary.Size())
            lap("tables")
            self._dp_trainer = None
            if opt.device_pairs:
                from multiverso_tpu.models.wordembedding.device_pairs import (
                    DevicePairsTrainer)
                self._dp_trainer = DevicePairsTrainer(opt, self.comm, counts,
                                                      huffman=self.huffman)
            self._step = make_train_step(opt.use_adagrad)
            lap("trainer")
        for part, seconds in laps.items():
            tmetrics.gauge(f"we.prepare.{part}_s").set(seconds)

    # -- training -----------------------------------------------------------

    def train(self) -> float:
        """Returns average pair loss of the run.

        Loss fetches lag one block behind the dispatches: forcing the
        scanned program's scalar right away would serialize host prep with
        the device work, so the loop keeps one result in flight and the
        per-block log line reports the average over *completed* blocks."""
        import collections
        opt = self.opt
        generator = PairGenerator(opt, self.dictionary, self.sampler,
                                  self.huffman)
        queue = BlockQueue(capacity=3 if opt.is_pipeline else 1)
        loader = start_loader(opt, self.dictionary, generator, queue,
                              opt.epoch)
        step = self._step
        timer = Timer()
        words_done = 0
        self.total_loss = 0.0
        self.total_pairs = 0
        pending = collections.deque()
        self._blocks_done = 0
        m_pop_wait = tmetrics.histogram("we.pop_wait_s")
        m_blocks = tmetrics.counter("we.blocks")
        # registered at 0 beside it: the blocks whose scan took the
        # touched-rows step (_block_scan_fn)
        tmetrics.counter("we.block_scan.touched_rows_blocks")
        m_steps_run = tmetrics.counter("we.block.steps.run")
        # a -device_pairs block's touched-rows updates: the row lanes the
        # device ran them at, of those the steps run laid out
        m_lanes_run = tmetrics.counter("we.update.lanes.run")
        m_lanes_laid_out = tmetrics.counter("we.update.lanes.laid_out")
        # registered at 0 too: the blocks that trained on prefetched rows
        tmetrics.counter("we.pipeline.prefetched_blocks")

        def harvest(force: bool = False) -> None:
            while pending and (force or len(pending) >= 2):
                loss, pairs, ctx = pending.popleft()
                # the block's make_block span is the parent: one tree
                # from the loader to the harvest
                with ttrace.span("worker.we.harvest", cat="worker",
                                 parent=ctx):
                    self.total_loss += float(loss)
                    # -device_pairs blocks report the pair count as a
                    # device scalar (the program derives the pairs);
                    # int() fetches it
                    self.total_pairs += int(pairs)
                    if hasattr(pairs, "lane"):
                        # a -device_pairs block: the steps its program's
                        # loop ran, of the nb laid out (third lane of the
                        # copy just made), and the row lanes their
                        # updates ran (the fourth) of those they laid out
                        steps = int(pairs.lane(2))
                        m_steps_run.inc(steps)
                        m_lanes_run.inc(int(pairs.lane(3)))
                        m_lanes_laid_out.inc(
                            steps * self._dp_trainer.step_update_lanes)

        from multiverso_tpu.parallel import multihost
        from multiverso_tpu.utils.log import CHECK
        multiproc = multihost.process_count() > 1

        def pop_block():
            """queue.pop, made multi-process-safe: per-block table verbs
            are COLLECTIVE, so a rank whose shard ran out must not stop
            calling them while peers continue (a silent distributed
            hang). ONE allgather per block agrees on global completion
            and, for -device_pairs, on the shared token bucket and
            sentence-id span — finished ranks then keep participating
            with EMPTY filler blocks until everyone is done. The other
            planes cannot run an empty block through their row verbs, so
            ragged shard streams fail LOUDLY there instead (shard
            corpora evenly, or use -device_pairs)."""
            t0 = time.perf_counter()
            with ttrace.span("worker.we.pop_wait", cat="worker"):
                block = queue.pop()
            m_pop_wait.observe(time.perf_counter() - t0)
            if not multiproc:
                return block
            T = len(block.tokens) if (block is not None
                                      and block.tokens is not None) else 0
            max_sent = (int(block.token_sent.max(initial=-1)) + 1
                        if block is not None and block.token_sent is not None
                        else 0)
            parts = multihost.host_allgather_objects_capped(
                (block is None, T, max_sent), "we_pop")
            if all(p[0] for p in parts):
                return None
            if any(p[0] for p in parts):
                # the gathered flags are REPLICATED knowledge: every rank
                # raises together, so the failure is loud on all of them
                # instead of stranding the live ranks in the next
                # collective behind one dead peer
                CHECK(opt.device_pairs,
                      "multi-process WE with unequal per-rank block "
                      "streams needs -device_pairs (empty filler blocks); "
                      "host/device-plane rounds cannot run empty — shard "
                      "the corpora evenly")
            if block is None:
                block = DataBlock(word_count=0,
                                  tokens=np.empty(0, np.int32),
                                  token_sent=np.empty(0, np.int32))
            if opt.device_pairs:
                # hand the agreed statics to train_block: the shared
                # bucket and the global sentence span (one allgather per
                # block total)
                block._dp_agreed = (max(p[1] for p in parts),
                                    max(p[2] for p in parts))
            return block

        # The block pipeline (-is_pipeline 1, host plane; reference
        # distributed_wordembedding.cpp:203-215), ONE worker, prefetch
        # depth one. The order holds by the order of this thread's sends
        # into the server's first-in-first-out mailbox, whatever windows
        # the engine cuts them into: block b+1's MV_MultiGetAsync is sent
        # before block b's Adds and after block b-1's, so its reply holds
        # block b-1's deltas and none of block b's. Blocks 0 and 1 of a
        # train() train on the tables as the call found them and block
        # b >= 2 on the tables with the deltas of blocks 0..b-2 and no
        # other; every delta is added exactly once
        # (tests/test_we_pipeline.py holds this under a slow server).
        current = pop_block()
        prefetch = None
        next_block: Optional[DataBlock] = None
        while current is not None:
            if opt.is_pipeline:
                next_block = pop_block()
                # host-plane prefetch only: the device plane's fetch is an
                # async dispatch already (nothing to overlap by hand)
                if (next_block is not None and next_block.pair_count
                        and not opt.device_plane):
                    with ttrace.span("worker.we.prefetch.issue",
                                     cat="worker",
                                     parent=next_block.trace_ctx):
                        prefetch = self.comm.request_parameter_async(
                            next_block.input_rows, next_block.output_rows)
            loss, pairs = self._train_block(current, step)
            m_blocks.inc()
            pending.append((loss, pairs, current.trace_ctx))
            harvest()
            words_done += current.word_count
            self.comm.add_word_count(current.word_count)
            rate = words_done / max(timer.elapse(), 1e-9)
            Log.Info("[wordembedding] %d words (%.0f words/s), "
                     "avg pair loss %.4f, lr %.5f", words_done, rate,
                     self.total_loss / max(self.total_pairs, 1),
                     self._current_lr())
            if opt.is_pipeline:
                if next_block is not None and next_block.pair_count \
                        and prefetch is not None:
                    # after block b's push, as the order above has it:
                    # the wait, then the next block's training copy
                    with ttrace.span("worker.we.fetch", cat="worker",
                                     parent=next_block.trace_ctx):
                        fetched = self.comm.wait_rows(prefetch)
                    with ttrace.span("worker.we.upload", cat="worker",
                                     parent=next_block.trace_ctx):
                        next_block._prefetched = (
                            self.comm.training_state(fetched), fetched)
                current, prefetch = next_block, None
            else:
                current = pop_block()
        harvest(force=True)
        loader.join()  # unbounded-ok: loader terminates with the corpus
        return self.total_loss / max(self.total_pairs, 1)

    def _current_lr(self) -> float:
        opt = self.opt
        if opt.use_adagrad:
            return opt.init_learning_rate
        return decayed_lr(opt.init_learning_rate, self.comm.get_word_count(),
                          opt.total_words, opt.epoch)

    def _block_scan_fn(self, state, step):
        """-> (program, touched): one jit'd program looping a train step
        over a whole block's stacked batches, so a block pays ONE upload +
        ONE dispatch instead of one per batch, and whether that step is
        the touched-rows one.

        The loop runs ``n_live`` steps, the program's last operand (an
        int32 scalar), and not the ``nb`` its inputs are laid out in:
        ``make_block`` fills the pair axis in order, so the batches that
        hold a pair are the first ``ceil(pair_count / pair_batch_size)``
        and ``nb`` is that count rounded up to a bucket. A batch past them
        is all zero masks: zero gradients, zero loss, every row it names
        rewritten with what was read from it, so leaving it out leaves the
        state and the loss (the sum over all ``nb`` lanes, a batch not run
        keeping the zero it would return) equal to the bit. The count is
        an operand and ``nb`` a shape: blocks inside one bucket share one
        compiled program (``_steps_to_run`` has the host's side).

        The step is chosen from the ``state`` the program is handed. Under
        AdaGrad ``step`` (model.make_train_step) streams every row of the
        state a batch: fast while the state is small. Once the larger of
        ``state.ie`` / ``state.eo`` passes ``device_pairs._SPARSE_BYTES``
        (the one constant of this trade: -device_pairs chooses by it too)
        the scan takes ``device_pairs._make_sparse_adagrad_step``, the same
        arithmetic on the rows a batch names; its pad lanes go to the
        state's LAST row, the trash row of the communicator's training
        copy. That step's row write is a Mosaic kernel, which the compiler
        cannot partition: a state laid over more than one device keeps
        ``step``, as plain SGD does at any size (its update is the
        scatter-add already).

        A program retraces per distinct ``nb`` and state rung, which
        block sizing and ``communicator.training_rows`` keep to a handful.
        Kept for the trainer's life under what it depends on,
        ``use_adagrad`` (which fixes ``step``) and the choice, so a later
        train() dispatches the programs the first one traced."""
        from multiverso_tpu.models.wordembedding import device_pairs
        use_adagrad = bool(self.opt.use_adagrad)
        touched = (use_adagrad
                   and max(rows.size * rows.dtype.itemsize
                           for rows in (state.ie, state.eo))
                   > device_pairs._SPARSE_BYTES
                   and all(len(rows.sharding.device_set) == 1
                           for rows in state if rows is not None))
        if (use_adagrad, touched) not in self._block_scan_cache:
            import jax
            import jax.numpy as jnp
            from jax import lax

            if touched:
                # (state, loss) like ``step``: the lanes its updates ran
                # are the -device_pairs harvest's to count
                sparse = device_pairs._make_sparse_adagrad_step()
                step = lambda *args: sparse(*args)[:2]  # noqa: E731

            def run(state, inputs, imask, outputs, labels, omask, lr,
                    n_live):
                stacked = (inputs, imask, outputs, labels, omask)

                def body(i, carry):
                    st, losses = carry
                    st, loss = step(st, *(lax.dynamic_index_in_dim(
                        a, i, keepdims=False) for a in stacked), lr)
                    return st, losses.at[i].set(loss)
                # a stable name in the device trace's op metadata
                with jax.named_scope("we.block_scan"):
                    st, losses = lax.fori_loop(
                        0, n_live, body,
                        (state, jnp.zeros((inputs.shape[0],), jnp.float32)))
                    return st, jnp.sum(losses)

            # donate the block state: the fetch path hands this jit its own
            # buffers (the communicator's training copy keeps the originals
            # alive for the delta push), so the scan may update the row
            # matrices in place
            self._block_scan_cache[use_adagrad, touched] = jax.jit(
                run, donate_argnums=(0,))
            tmetrics.counter("we.block_program.builds").inc()
        return self._block_scan_cache[use_adagrad, touched], touched

    def _train_block(self, block: DataBlock, step) -> tuple:
        """One block through the scanned program. Returns (loss, pairs)
        where both may be DEVICE scalars (the caller harvests lazily so
        the dispatch overlaps the next block's prep). The dispatches are
        asynchronous, so ``worker.we.block`` is the host's part of a
        block; its children name that part."""
        index, self._blocks_done = self._blocks_done, self._blocks_done + 1
        with ttrace.span("worker.we.block", cat="worker",
                         parent=block.trace_ctx,
                         args=({"block": index,
                                "words": int(block.word_count)}
                               if ttrace.enabled() else None)):
            return self._train_block_body(block, step)

    def _train_block_body(self, block: DataBlock, step) -> tuple:
        if self.opt.device_pairs and block.tokens is not None:
            # fused generate+train: the tiny token stream is the upload
            return self._dp_trainer.train_block(
                block.tokens, block.token_sent, self._current_lr(),
                agreed=getattr(block, "_dp_agreed", None))
        if not block.pair_count:
            return 0.0, 0
        import jax.numpy as jnp
        st = block.stacked
        # before the fetch, which may wait for the last block's applies
        # (communicator.request_parameter_device): the copies ride under
        # them
        with ttrace.span("worker.we.upload", cat="worker"):
            tensors = [jnp.asarray(st[k]) for k in (
                "inputs", "input_mask", "outputs", "labels", "output_mask")]
        # taken off the block: whoever keeps the block keeps no rows
        pre = block.__dict__.pop("_prefetched", None)
        if pre is not None:
            state, fetched = pre    # train() waited for it, under its span
            tmetrics.counter("we.pipeline.prefetched_blocks").inc()
        else:
            with ttrace.span("worker.we.fetch", cat="worker"):
                if self.opt.device_plane:
                    # rows gathered, trained, and pushed without leaving
                    # HBM; the loader threads prebuilt the remapped
                    # stacked tensors, so the block rides one upload + one
                    # scanned dispatch
                    state, fetched = self.comm.request_parameter_device(
                        block.input_rows, block.output_rows)
                else:
                    state, fetched = self.comm.request_parameter(
                        block.input_rows, block.output_rows)
        with ttrace.span("worker.we.dispatch", cat="worker"):
            program, touched = self._block_scan_fn(state, step)
            nb = tensors[0].shape[0]
            n_live = _steps_to_run(state, block.pair_count,
                                   self.opt.pair_batch_size, nb)
            state, loss_dev = program(
                state, *tensors, jnp.float32(self._current_lr()),
                np.int32(n_live))
            # host integers both: no device read (-device_pairs steps the
            # same pair, .run at the harvest from its program's count)
            tmetrics.counter("we.block.steps.laid_out").inc(nb)
            tmetrics.counter("we.block.steps.run").inc(n_live)
            if touched:
                tmetrics.counter("we.block_scan.touched_rows_blocks").inc()
        with ttrace.span("worker.we.push", cat="worker"):
            if self.opt.device_plane:
                self.comm.add_delta_parameter_device(
                    state, fetched, block.input_rows, block.output_rows)
            else:
                self.comm.add_delta_parameter(state, fetched,
                                              block.input_rows,
                                              block.output_rows)
        return loss_dev, block.pair_count

    # -- export (word2vec format) -------------------------------------------

    def save_embeddings(self, path: Optional[str] = None) -> None:
        path = path or self.opt.output_file
        emb = self.comm.pull_embeddings()
        words = self.dictionary.words()
        if self.opt.output_binary:
            with open(path, "wb") as f:
                f.write(f"{len(words)} {self.opt.embedding_size}\n"
                        .encode())
                for w, row in zip(words, emb):
                    f.write(w.encode("utf-8") + b" ")
                    f.write(np.asarray(row, np.float32).tobytes())
                    f.write(b"\n")
        else:
            with open(path, "w", encoding="utf-8") as f:
                f.write(f"{len(words)} {self.opt.embedding_size}\n")
                for w, row in zip(words, emb):
                    f.write(w + " " + " ".join(f"{x:.6f}" for x in row) + "\n")
        Log.Info("[wordembedding] saved %d x %d embeddings to %s",
                 len(words), self.opt.embedding_size, path)

    # -- lifecycle ----------------------------------------------------------

    def run(self) -> float:
        """Full job (reference Run, distributed_wordembedding.cpp:366).

        Exception-safe end to end: a raise anywhere after MV_Init (training,
        export) shuts down the world this driver started, so the process /
        test suite never inherits a stranded Zoo. Success leaves the world
        up — the caller owns close()."""
        self.prepare()
        with self._world.guard("wordembedding.run"):
            avg_loss = self.train()
            mv.MV_Barrier()
            if mv.MV_WorkerId() == 0:
                self.save_embeddings()
        return avg_loss

    def close(self) -> None:
        self._world.close()


def main(argv=None) -> int:
    import sys
    argv = argv if argv is not None else sys.argv[1:]
    opt = Option.parse_args(argv)
    if not opt.train_file:
        Log.Error("usage: python -m multiverso_tpu.models.wordembedding."
                  "distributed -train_file corpus.txt [-size 100 ...]")
        return 1
    opt.print_args()
    compile_cache.enable()
    we = DistributedWordEmbedding(opt)
    we.run()
    we.close()
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
