"""App-level communicator: the WordEmbedding parameter tables.

Behavioral equivalent of reference
Applications/WordEmbedding/src/communicator.h/.cpp: owns 4 matrix tables —
input embeddings, output embeddings, and (when AdaGrad) the two
sum-of-squared-gradient tables — plus the int64 KV word-count table
(communicator.cpp:17-33, table ids constant.h:16-20). ``RequestParameter``
fetches the block's touched rows (communicator.cpp:117); ``AddDeltaParameter``
pushes back ``trained - fetched`` (communicator.cpp:157-206) so concurrent
workers' progress merges additively on the default (+=) server updater.

Where a block's rows live between its fetch and its push. On both planes
a fetch leaves two things on the device: the block's ORIGINALS by state
field and the training copy the block's scan is handed and donates
(``training_rows`` long); the push makes ``trained - original`` there, in
the originals' buffers, which it consumes. The device plane gathers the
rows out of the sharded stores and scatters the delta back: nothing
crosses. The host plane gets them through the server as numpy arrays
(``MV_MultiGetAsync``) and sends the delta as one (``AddFireForget``): its
rows cross the boundary ONCE each way (``training_state`` in,
``add_delta_parameter`` back) and no line of the host passes over them in
between. Every program of that path is keyed by the training RUNG (with
the width and the dtype), or by a piece count (``_join_row_pieces``):
never by a block's row count, which differs every block and every seed.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import multiverso_tpu as mv
from multiverso_tpu.models.wordembedding.model import TrainState, init_embedding
from multiverso_tpu.parallel.mesh import next_bucket
from multiverso_tpu.tables import KVTableOption, MatrixTableOption
from multiverso_tpu.tables import matrix_table
from multiverso_tpu.tables.matrix_table import _pad_row_batch
from multiverso_tpu.telemetry import metrics as tmetrics
from multiverso_tpu.telemetry import trace as ttrace

WORD_COUNT_KEY = 0


def training_rows(fetched_rows: int) -> int:
    """Rows of the training copy of a block's ``fetched_rows`` rows: the
    ``next_bucket`` rung with at least ONE row to spare. The spare rows
    are zero and the last is the trash row the touched-rows AdaGrad step
    sends its pad lanes to (``device_pairs._make_sparse_adagrad_step``: a
    block's last fetched row is a live word). A rung, not a count, is
    also what the block's scan program is compiled for: blocks whose row
    counts differ inside a rung share one program."""
    return next_bucket(fetched_rows + 1)


@functools.partial(jax.jit, donate_argnums=(1,))
def _trained_delta(trained: jax.Array, fetched: jax.Array) -> jax.Array:
    """trained - fetched over the fetched rows, written where the fetched
    rows were: the spare rows of the training copy are cut in the same
    program and the delta is no row set more in HBM."""
    return trained[: fetched.shape[0]] - fetched


def _place_at_rung(rows: np.ndarray, rung: int) -> jax.Array:
    """A reply's host rows on the device at ``rung`` rows, zeros below
    them, the way a host delta crosses (``matrix_table._place_rows``):
    views of the reply's array in eighths of the rung and one
    ``_join_row_pieces`` program a piece count; no pad, no zeroing and no
    copy on the host. The table layer sends a batch whose pad is under
    its ``_HOST_CUT_PAD_BYTES`` exact-size and pads it by a program a
    distinct count; a block's counts never repeat, so such a batch (a
    block's input rows: tens of MB) is padded here on the host,
    milliseconds, and crosses at the rung."""
    spare = rung - len(rows)
    if spare * rows[:1].nbytes <= matrix_table._HOST_CUT_PAD_BYTES:
        return jnp.asarray(np.pad(rows, ((0, spare), (0, 0))))
    return matrix_table._place_rows(rows, rung)


@jax.jit
def _training_copy(original: jax.Array) -> jax.Array:
    """The buffer a host-plane block's scan is handed and donates, made
    on the device of the original, which stays for the delta. One program
    a rung."""
    return jnp.copy(original)


@functools.partial(jax.jit, donate_argnums=(1,))
def _rung_delta(trained: jax.Array, original: jax.Array) -> jax.Array:
    """trained - original over the WHOLE training rung, written where the
    original was: one program a rung (``_trained_delta``'s shape is the
    row count, which the device plane's fetch fixes). Below the fetched
    rows it gives 0 - 0 and, in the last row, what the scan's pad lanes
    wrote to the trash row: the caller's ``[:n]`` of the host copy cuts
    both."""
    return trained - original


class Communicator:
    def __init__(self, option, vocab_size: int):
        self.opt = option
        self.vocab_size = vocab_size
        dim = option.embedding_size
        seed = option.seed
        # output-embedding rows: HS uses vocab_size-1 inner nodes but we
        # allocate vocab_size for both modes like the reference
        self.input_table = mv.MV_CreateTable(MatrixTableOption(
            num_rows=vocab_size, num_cols=dim,
            initializer=lambda shape: init_embedding(shape[0], shape[1], seed)))
        self.output_table = mv.MV_CreateTable(MatrixTableOption(
            num_rows=vocab_size, num_cols=dim))  # zeros like word2vec syn1
        self.ie_g2_table = None
        self.eo_g2_table = None
        if option.use_adagrad:
            self.ie_g2_table = mv.MV_CreateTable(MatrixTableOption(
                num_rows=vocab_size, num_cols=dim))
            self.eo_g2_table = mv.MV_CreateTable(MatrixTableOption(
                num_rows=vocab_size, num_cols=dim))
        self.word_count_table = mv.MV_CreateTable(KVTableOption(dtype=np.int64))

    # -- parameter movement -------------------------------------------------

    def _row_specs(self, input_rows, output_rows):
        """(state field, table, the block's row ids) of every table."""
        specs = [("ie", self.input_table, input_rows),
                 ("eo", self.output_table, output_rows)]
        if self.opt.use_adagrad:
            specs += [("ie_g2", self.ie_g2_table, input_rows),
                      ("eo_g2", self.eo_g2_table, output_rows)]
        return specs

    def request_parameter(self, input_rows: np.ndarray,
                          output_rows: np.ndarray) -> Tuple[TrainState, dict]:
        """Fetch the block's rows; returns (device state, fetched): the
        pair ``request_parameter_device`` returns. ``fetched`` holds the
        block's originals ON THE DEVICE by state field
        (``training_state`` put them where the reply's host rows were);
        ``add_delta_parameter`` consumes them.

        Issues every table's Get asynchronously BEFORE waiting any
        (round 7): the engine drains the burst into one window — one
        host exchange serves all four tables in a 2-proc world instead
        of four blocking round trips, and under the pipelined engine
        the previous block's delta pushes apply while this exchange is
        on the wire. The reference's sequential blocking fetch
        (communicator.cpp:117-155) was the WE app's 2-proc
        anti-scaling hot spot (BENCH_r05)."""
        fetched = self.wait_rows(
            self.request_parameter_async(input_rows, output_rows))
        return self.training_state(fetched), fetched

    def request_parameter_async(self, input_rows: np.ndarray,
                                output_rows: np.ndarray) -> dict:
        """Issue async row gets for the NEXT block (pipeline prefetch,
        reference distributed_wordembedding.cpp:203-215). Round 19: the
        2-4 per-table round trips became ONE batched submission
        (MV_MultiGetAsync) — one mailbox hop, one window admission, one
        reply wake-up for the whole block's parameter set (the per-verb
        round trip was the 2-proc WE app's anti-scaling hot spot,
        BENCH_r05)."""
        ids_in = np.asarray(input_rows, np.int32)
        ids_out = np.asarray(output_rows, np.int32)
        ops = [(self.input_table, {"row_ids": ids_in}),
               (self.output_table, {"row_ids": ids_out})]
        names = ["ie", "eo"]
        if self.opt.use_adagrad:
            ops += [(self.ie_g2_table, {"row_ids": ids_in}),
                    (self.eo_g2_table, {"row_ids": ids_out})]
            names += ["ie_g2", "eo_g2"]
        from multiverso_tpu import api as mv_api
        return {"call": mv_api.MV_MultiGetAsync(ops), "names": names}

    def wait_rows(self, handles: dict) -> dict:
        """The reply of ``request_parameter_async``: every table's rows on
        the host, by state field. The row bytes a block's Gets return are
        counted here, once."""
        # unbounded-ok: MultiCall.Wait honors -mv_deadline_s internally
        fetched = dict(zip(handles["names"], handles["call"].Wait()))
        tmetrics.counter("we.host_plane.fetched_bytes").inc(
            sum(rows.nbytes for rows in fetched.values()))
        return fetched

    def training_state(self, fetched: dict) -> TrainState:
        """The device state a block trains, of a reply's host rows; ON
        RETURN ``fetched`` HOLDS THE BLOCK'S ORIGINALS ON THE DEVICE in
        their place, by state field, and the host keeps no copy of the
        reply. Each table's rows cross once, as the reply holds them, and
        lie at ``training_rows`` rows there (``_place_at_rung``: zeros
        below the rows, the last the trash row). That array is the
        original, kept in HBM for the delta; the state is a copy of it
        made on the device (``_training_copy``), a buffer of its own
        because the scan donates it. The same training copy as the device
        plane's: one state shape a rung, whichever plane fetched it."""
        train = {}
        for name, rows in fetched.items():
            fetched[name] = _place_at_rung(rows, training_rows(len(rows)))
            train[name] = _training_copy(fetched[name])
        return TrainState(ie=train["ie"], eo=train["eo"],
                          ie_g2=train.get("ie_g2"), eo_g2=train.get("eo_g2"))

    def add_delta_parameter(self, state: TrainState, fetched: dict,
                            input_rows: np.ndarray,
                            output_rows: np.ndarray) -> None:
        """Push trained - fetched (reference AddDeltaParameter,
        communicator.cpp:157-206). ``fetched`` holds the block's
        originals on the device (``training_state``) and is consumed: the
        subtraction runs there over the whole rung, in the originals'
        buffers (``_rung_delta``: float32, the host's ``trained -
        fetched`` to the bit). Every table's program is dispatched and
        its copy back started before the first is taken; then table after
        table, in the order ``ie``, ``eo``, ``ie_g2``, ``eo_g2``, the
        rung's host array is taken and ``AddFireForget`` is handed the
        view of its first ``len(ids)`` rows, so the server applies one
        table's delta while the next crosses. That array is READ-ONLY (a
        device array's host copy): nothing on an Add's way writes to a
        payload. Children of the caller's span (``worker.we.push``), one
        of each a table: ``.delta`` the dispatches, ``.take`` the wait
        for the copy back (the first waits for the block's program),
        ``.add`` the ``AddFireForget``. The row bytes a block's Adds send
        are counted here, once."""
        specs = self._row_specs(input_rows, output_rows)
        deltas = []
        for name, _, _ in specs:
            with ttrace.child(".delta"):
                delta = _rung_delta(getattr(state, name), fetched.pop(name))
                delta.copy_to_host_async()
            deltas.append(delta)
        pushed = 0
        for (_, table, ids), delta in zip(specs, deltas):
            with ttrace.child(".take"):
                rows = np.asarray(delta)[: len(ids)]
            with ttrace.child(".add"):
                table.AddFireForget(rows, row_ids=ids)
            pushed += rows.nbytes
        tmetrics.counter("we.host_plane.pushed_bytes").inc(pushed)

    # -- device plane (rows never leave HBM) --------------------------------

    def request_parameter_device(self, input_rows: np.ndarray,
                                 output_rows: np.ndarray
                                 ) -> Tuple[TrainState, dict]:
        """Device-plane fetch: gather the block's rows straight out of the
        sharded stores (docs/DESIGN.md §4) — the TrainState AND the
        originals kept for the delta push stay in HBM. Single-writer per
        process: the caller owns the tables while training (the app's
        block loop is sequential; reference omp-thread sharing is the
        host plane's job). Multi-process the verbs are collective — the
        same lockstep block-loop contract the host-plane tables already
        impose on this app — and per-process row sets merge on device."""
        rows = {}
        train = {}
        for name, table, ids in self._row_specs(input_rows, output_rows):
            srv = table.server()
            # a fetch allocates its rows when it is dispatched: wait until
            # this table's last apply has run and freed the last block's,
            # or the host runs a block ahead and HBM holds two blocks' row
            # sets (7.8 -> 11.3 GB at 1,048,500 words; PERF.md, PR 26)
            jax.block_until_ready(srv.state)
            rows[name] = srv.device_fetch_rows(ids)
            # the train step DONATES its state; the original must survive
            # for the delta push, so the state gets its own buffer: a
            # rung long, zeros below the rows, the last the touched-rows
            # step's trash row (the apply's own pad program: where both
            # rungs are one, as nearly always, one compiled program)
            train[name] = _pad_row_batch(rows[name],
                                         training_rows(len(ids)))
        state = TrainState(ie=train["ie"], eo=train["eo"],
                           ie_g2=train.get("ie_g2"),
                           eo_g2=train.get("eo_g2"))
        return state, rows

    def add_delta_parameter_device(self, state: TrainState, fetched: dict,
                                   input_rows: np.ndarray,
                                   output_rows: np.ndarray) -> None:
        """Push trained - fetched without leaving the device: the delta is
        computed in HBM, in the buffers of ``fetched`` (which this call
        consumes), and scattered into the store by the same jit'd row
        program the engine uses."""
        for name, table, ids in self._row_specs(input_rows, output_rows):
            delta = _trained_delta(getattr(state, name), fetched[name])
            table.server().device_apply_rows(ids, delta)

    # -- word count (lr decay coordination) ---------------------------------

    def add_word_count(self, count: int) -> None:
        self.word_count_table.Add([WORD_COUNT_KEY], [count])

    def get_word_count(self) -> int:
        return int(self.word_count_table.Get([WORD_COUNT_KEY])[0])

    # -- export -------------------------------------------------------------

    def pull_embeddings(self, batch: int = 4096) -> np.ndarray:
        """Whole input-embedding matrix via batched row gets
        (reference SaveEmbedding, distributed_wordembedding.cpp:263-306)."""
        rows = []
        for start in range(0, self.vocab_size, batch):
            ids = np.arange(start, min(start + batch, self.vocab_size),
                            dtype=np.int32)
            rows.append(self.input_table.GetRows(ids))
        return np.vstack(rows)
