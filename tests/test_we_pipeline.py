"""The WordEmbedding app's default mode, the block pipeline on the host
plane (``-is_pipeline 1``: rows through the server by Get and Add, the next
block's rows prefetched), against its plain reference
(``models/wordembedding/pipeline_reference.py``); the order it keeps; the
counters of what crosses the boundary; that a Get or an Add of a
block's row set compiles no program for its row count and copies nothing
on the host; and that between a reply and a push a block's rows live on
the device alone: the training copy and the delta are made there, bit for
bit what ``np.pad`` and the host's ``trained - fetched`` made, by programs
keyed by the training rung.
"""

import os

import jax
import numpy as np
import pytest

from multiverso_tpu.models.wordembedding import communicator
from multiverso_tpu.models.wordembedding import pipeline_reference as ref
from multiverso_tpu.models.wordembedding.option import Option
from multiverso_tpu.tables import matrix_table
from multiverso_tpu.telemetry import metrics

VOCAB, TOPIC, DIM, SEED, LR = 4000, 10, 16, 7, 0.05

#: what the app and the reference may differ by, and why: both train the
#: very pair stream in float32, the app by a scanned program over a padded
#: copy of the rows, the reference batch by batch, so they differ by the
#: order of float32 sums at most (measured here, on the CPU: the average
#: loss 9e-9 apart, the four tables equal to the last bit). The limits
#: leave room for a backend that sums in another order and are a
#: thousandth of what one block's staleness changes (against the
#: sequential round the same run is 3.6e-3 apart in the loss and 14 in
#: an accumulator's entry; two blocks stale 4.6e-3 and 25)
LOSS_REL_TOL, ROW_ABS_TOL = 2e-6, 2e-5

_COMPILES = []
jax.monitoring.register_event_duration_secs_listener(
    lambda name, secs, **kw: _COMPILES.append(name)
    if name == "/jax/core/compile/backend_compile_duration" else None)


def _counter(name):
    return metrics.counter(name).value


def _write_corpus(tmp_path, sentences=450, seed=0):
    """A vocabulary file (word2vec's ``word count`` lines) of VOCAB words
    and a corpus over it: sentences of 12 words of one topic of TOPIC
    words, topics and words within a topic Zipf-distributed. The tail is
    long, so a block's negatives name a different row set every pass and
    under half the vocabulary (over it the app fetches every row).
    -> (vocabulary path, corpus path)."""
    rng = np.random.default_rng(seed)
    p_topic = 1.0 / np.arange(1, VOCAB // TOPIC + 1)
    p_word = 1.0 / np.arange(1, TOPIC + 1)
    p_topic, p_word = p_topic / p_topic.sum(), p_word / p_word.sum()
    counts = np.maximum(1, np.rint(np.outer(p_topic, p_word).ravel() * 1e6))
    vocab, corpus = tmp_path / "vocab.txt", tmp_path / "corpus.txt"
    vocab.write_text("".join(f"w{i} {int(c)}\n"
                             for i, c in enumerate(counts)))
    topics = rng.choice(len(p_topic), sentences, p=p_topic)
    with open(corpus, "w") as f:
        for t in topics:
            words = rng.choice(TOPIC, 12, p=p_word)
            f.write(" ".join(f"w{t * TOPIC + w}" for w in words) + "\n")
    return str(vocab), str(corpus)


class _App:
    """The app on the host plane, its blocks kept as the reference takes
    them."""

    def __init__(self, tmp_path, **options):
        from multiverso_tpu.models.wordembedding.distributed import (
            DistributedWordEmbedding)
        vocab, corpus = _write_corpus(tmp_path)
        opt = Option(train_file=corpus, read_vocab_file=vocab,
                     output_file=str(tmp_path / "vec.txt"),
                     embedding_size=DIM, window_size=2, negative_num=2,
                     min_count=1, epoch=2, data_block_size=5000,
                     pair_batch_size=256, init_learning_rate=LR,
                     use_adagrad=True, seed=SEED)
        for key, value in options.items():
            setattr(opt, key, value)
        self.we = DistributedWordEmbedding(opt)
        self.we.prepare()
        self.blocks = []
        inner = self.we._train_block

        def train_block(block, step):
            st = block.stacked
            self.blocks.append({
                "input_rows": block.input_rows,
                "output_rows": block.output_rows,
                "batches": [{k: st[k][i] for k in st}
                            for i in range(st["inputs"].shape[0])]})
            return inner(block, step)

        self.we._train_block = train_block

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.we.close()

    def train(self):
        """-> (average pair loss, pairs, the four tables once every delta
        has landed: a Get queues behind the Adds of its table)."""
        loss = self.we.train()
        comm, rows = self.we.comm, np.arange(VOCAB, dtype=np.int32)
        tables = [np.array(t.GetRows(rows)) for t in (
            comm.input_table, comm.output_table, comm.ie_g2_table,
            comm.eo_g2_table)]
        return loss, int(self.we.total_pairs), tables


def _fresh_tables():
    zeros = np.zeros((VOCAB, DIM), np.float32)
    return (ref.init_input(VOCAB, DIM, SEED), zeros, zeros, zeros)


def _gaps(app_loss, pairs, tables, blocks, prefetch_depth):
    """(relative gap of the average loss, worst absolute gap an entry of
    the four tables) between the app and the reference at that depth."""
    total, want = ref.train_blocks(blocks, _fresh_tables(), LR,
                                   prefetch_depth=prefetch_depth)
    ref_pairs = int(sum(b["labels"].sum() for block in blocks
                        for b in block["batches"]))
    assert pairs == ref_pairs
    ref_loss = total / ref_pairs
    return (abs(app_loss - ref_loss) / ref_loss,
            max(float(np.abs(got - exp).max())
                for got, exp in zip(tables, want)))


@pytest.fixture(scope="module")
def pipelined(tmp_path_factory):
    """One train() of two passes with the pipeline on: (loss, pairs,
    tables, blocks, what the counters moved by)."""
    names = ("we.blocks", "we.pipeline.prefetched_blocks",
             "we.host_plane.fetched_bytes", "we.host_plane.pushed_bytes")
    with _App(tmp_path_factory.mktemp("pipelined"), is_pipeline=True) as app:
        before = [_counter(name) for name in names]
        loss, pairs, tables = app.train()
        moved = {name: _counter(name) - was
                 for name, was in zip(names, before)}
        return loss, pairs, tables, app.blocks, moved


def test_the_corpus_is_blocks_enough(pipelined):
    blocks = pipelined[3]
    assert len(blocks) >= 10 and len(blocks) % 2 == 0   # 2 passes of >= 5
    sets = {(len(b["input_rows"]), len(b["output_rows"])) for b in blocks}
    assert len(sets) >= 5           # the blocks name different row counts


def test_pipeline_equals_the_pipeline_reference(pipelined):
    loss, pairs, tables, blocks, _ = pipelined
    loss_gap, row_gap = _gaps(loss, pairs, tables, blocks, prefetch_depth=1)
    assert loss_gap <= LOSS_REL_TOL and row_gap <= ROW_ABS_TOL


def test_pipeline_is_not_the_sequential_round(pipelined):
    """The check tells the guarantee from a stronger one: the same run
    fails both limits against the reference that fetches after the last
    block's deltas."""
    loss, pairs, tables, blocks, _ = pipelined
    loss_gap, row_gap = _gaps(loss, pairs, tables, blocks, prefetch_depth=0)
    assert loss_gap > 100 * LOSS_REL_TOL and row_gap > 100 * ROW_ABS_TOL


def test_pipeline_is_not_two_blocks_stale(pipelined):
    """... and from a weaker one."""
    loss, pairs, tables, blocks, _ = pipelined
    loss_gap, row_gap = _gaps(loss, pairs, tables, blocks, prefetch_depth=2)
    assert loss_gap > 100 * LOSS_REL_TOL and row_gap > 100 * ROW_ABS_TOL


def test_without_the_pipeline_the_app_is_the_sequential_round(tmp_path):
    with _App(tmp_path, is_pipeline=False) as app:
        loss, pairs, tables = app.train()
        blocks = app.blocks
    loss_gap, row_gap = _gaps(loss, pairs, tables, blocks, prefetch_depth=0)
    assert loss_gap <= LOSS_REL_TOL and row_gap <= ROW_ABS_TOL
    loss_gap, row_gap = _gaps(loss, pairs, tables, blocks, prefetch_depth=1)
    assert loss_gap > 100 * LOSS_REL_TOL and row_gap > 100 * ROW_ABS_TOL


def test_counters_of_what_crosses_the_boundary(pipelined):
    blocks, moved = pipelined[3], pipelined[4]
    assert moved["we.blocks"] == len(blocks)
    # every block of a train() but the first trains on prefetched rows
    assert moved["we.pipeline.prefetched_blocks"] == len(blocks) - 1
    row_bytes = DIM * 4
    crossed = sum(2 * (len(b["input_rows"]) + len(b["output_rows"]))
                  * row_bytes for b in blocks)      # four tables a block
    assert moved["we.host_plane.fetched_bytes"] == crossed
    assert moved["we.host_plane.pushed_bytes"] == crossed


def test_the_sequential_round_prefetches_nothing(tmp_path):
    was = _counter("we.pipeline.prefetched_blocks")
    with _App(tmp_path, is_pipeline=False, epoch=1) as app:
        app.train()
    assert _counter("we.pipeline.prefetched_blocks") == was


def _record_the_sends(monkeypatch, events, after_adds=lambda: None):
    """``get`` / ``reply`` / ``add`` into ``events`` as the worker sends a
    block's Gets, has their reply and has sent its Adds (then calls
    ``after_adds``)."""
    from multiverso_tpu.models.wordembedding.communicator import Communicator
    issue, wait, push = (Communicator.request_parameter_async,
                         Communicator.wait_rows,
                         Communicator.add_delta_parameter)

    def issuing(self, *a):
        events.append("get")
        return issue(self, *a)

    def waiting(self, handles):
        got = wait(self, handles)
        events.append("reply")
        return got

    def pushing(self, *a):
        push(self, *a)
        events.append("add")
        after_adds()
    monkeypatch.setattr(Communicator, "request_parameter_async", issuing)
    monkeypatch.setattr(Communicator, "wait_rows", waiting)
    monkeypatch.setattr(Communicator, "add_delta_parameter", pushing)


def test_the_order_of_one_workers_sends(tmp_path, monkeypatch):
    """Block b+1's Gets are sent before block b's Adds and after block
    b-1's: the order the guarantee rests on (distributed.train)."""
    events = []
    _record_the_sends(monkeypatch, events)
    with _App(tmp_path, is_pipeline=True, epoch=1) as app:
        app.train()
        n = len(app.blocks)
    # block 1's Gets, block 0's own Gets and their reply, block 0's Adds,
    # block 1's reply; then a block: the next one's Gets, this one's
    # Adds, the next one's reply; the last block has nothing to prefetch
    assert events == (["get", "get", "reply", "add", "reply"]
                      + ["get", "add", "reply"] * (n - 2) + ["add"])


def test_the_order_holds_under_a_slow_server(tmp_path, monkeypatch):
    """The engine's thread is held at a prefetched Get until the worker
    has SENT the deltas of the block it trains meanwhile: they sit in the
    mailbox behind the Get (and may share its window). Block b+1's rows
    still hold no delta of block b: the run equals the pipeline's
    reference, and neither the sequential round nor two blocks stale."""
    import threading
    events, held, sent = [], [], threading.Event()
    _record_the_sends(monkeypatch, events, after_adds=sent.set)
    inner = matrix_table.MatrixServerTable.ProcessGetAsync

    def slow_get(self, *a, **kw):
        # a train()'s third Get on is a prefetch sent just before the
        # Adds of the block in training (the first is block 1's prefetch,
        # the second block 0's own fetch, which the worker waits for)
        if (self is app.we.comm.input_table.server()
                and events.count("get") >= 3):
            held.append(sent.wait(timeout=60))
            sent.clear()
        return inner(self, *a, **kw)
    monkeypatch.setattr(matrix_table.MatrixServerTable, "ProcessGetAsync",
                        slow_get)
    with _App(tmp_path, is_pipeline=True, epoch=1) as app:
        loss, pairs, tables = app.train()
        blocks = app.blocks
    assert len(held) >= len(blocks) - 2 >= 3 and all(held)
    loss_gap, row_gap = _gaps(loss, pairs, tables, blocks, prefetch_depth=1)
    assert loss_gap <= LOSS_REL_TOL and row_gap <= ROW_ABS_TOL
    for depth in (0, 2):
        loss_gap, row_gap = _gaps(loss, pairs, tables, blocks, depth)
        assert loss_gap > 100 * LOSS_REL_TOL and row_gap > 100 * ROW_ABS_TOL


# -- no program a row count --------------------------------------------------

def test_second_pass_compiles_no_program_a_row_count(tmp_path, monkeypatch):
    """After a pass that warms the shapes up, a pass whose blocks name
    other row counts (another draw of negatives) compiles no slice and no
    pad program, with every Get's pad, every Add's pad and every training
    copy's pad over the constant: at most a join program a piece count it
    had not seen. The worker's own two programs, the training copy and
    the delta, have the rung's shape and are not built again."""
    monkeypatch.setattr(matrix_table, "_HOST_CUT_PAD_BYTES", 0)
    names = ("table.get.host_cuts", "table.get.device_cuts",
             "table.add.host_pieces")
    with _App(tmp_path, is_pipeline=True, epoch=1) as app:
        app.train()
        first = {(len(b["input_rows"]), len(b["output_rows"]))
                 for b in app.blocks}
        del app.blocks[:]
        before = [_counter(name) for name in names]
        compiled = len(_COMPILES)
        pads = matrix_table._pad_row_batch._cache_size()
        joins = matrix_table._join_row_pieces._cache_size()
        copies = communicator._training_copy._cache_size()
        deltas = communicator._rung_delta._cache_size()
        app.train()
        second = {(len(b["input_rows"]), len(b["output_rows"]))
                  for b in app.blocks}
        assert len(second - first) >= 3  # row counts the warm-up never saw
        assert matrix_table._pad_row_batch._cache_size() == pads
        new_joins = matrix_table._join_row_pieces._cache_size() - joins
        assert len(_COMPILES) - compiled == new_joins <= 1
        assert communicator._training_copy._cache_size() == copies
        assert communicator._rung_delta._cache_size() == deltas
        host, device, pieces = (_counter(name) - was
                                for name, was in zip(names, before))
        # a bucket over 256 rows keeps its pad under a quarter of the rows
        # and is carried; a shorter one is cut to an eighth of the bucket
        assert host > 0 and host + device > 0
        # four training copies and four Adds a block, seven or eight
        # pieces each
        assert 8 * 7 * len(app.blocks) <= pieces <= 8 * 8 * len(app.blocks)


# -- a block's rows between its reply and its push ---------------------------

def _record_a_blocks_rows(monkeypatch):
    """-> (replies, trained, sent): a block each, in the worker's order,
    the host rows a reply held (copies), the trained state as the push
    was handed it (host copies) and the (table, payload, a copy of the
    payload as it was handed over, ids) of every ``AddFireForget``."""
    Communicator = communicator.Communicator
    replies, trained, sent = [], [], []
    wait, push, add = (Communicator.wait_rows,
                       Communicator.add_delta_parameter,
                       matrix_table.MatrixWorkerTable.AddFireForget)

    def waiting(self, handles):
        got = wait(self, handles)
        replies.append({name: np.array(rows) for name, rows in got.items()})
        return got

    def pushing(self, state, fetched, *ids):
        # the originals, on the device: no host row rides on a block
        assert all(isinstance(rows, jax.Array) for rows in fetched.values())
        trained.append({name: np.array(getattr(state, name))
                        for name in fetched})
        return push(self, state, fetched, *ids)

    def adding(self, deltas, row_ids=None, option=None):
        sent.append((self, deltas, np.array(deltas), np.array(row_ids)))
        return add(self, deltas, row_ids=row_ids, option=option)
    monkeypatch.setattr(Communicator, "wait_rows", waiting)
    monkeypatch.setattr(Communicator, "add_delta_parameter", pushing)
    monkeypatch.setattr(matrix_table.MatrixWorkerTable, "AddFireForget",
                        adding)
    return replies, trained, sent


def _tables_of(comm):
    """(state field, table) in the order a push sends."""
    return [(name, table) for name, table, _ in comm._row_specs(None, None)]


@pytest.mark.parametrize("use_adagrad", [True, False],
                         ids=["adagrad", "sgd"])
@pytest.mark.parametrize("is_pipeline", [True, False],
                         ids=["pipeline", "sequential"])
def test_every_delta_is_the_hosts_subtraction_to_the_bit(
        tmp_path, monkeypatch, is_pipeline, use_adagrad):
    """The payload each ``AddFireForget`` receives is ``trained[:n] -
    fetched`` of the same float32 operands, ``n`` rows long, for every
    table of every block in the order ie, eo, ie_g2, eo_g2; it is
    read-only and, once every delta has landed, holds what it held when
    it was handed over."""
    replies, trained, sent = _record_a_blocks_rows(monkeypatch)
    with _App(tmp_path, is_pipeline=is_pipeline, use_adagrad=use_adagrad,
              epoch=1) as app:
        pushed = _counter("we.host_plane.pushed_bytes")
        app.we.train()
        pushed = _counter("we.host_plane.pushed_bytes") - pushed
        tables = _tables_of(app.we.comm)
        for _, table in tables:     # a Get queues behind its table's Adds
            table.GetRows(np.arange(8, dtype=np.int32))
        blocks = app.blocks
    per_block = 4 if use_adagrad else 2
    assert len(tables) == per_block
    assert len(replies) == len(trained) == len(blocks) >= 5
    assert len(sent) == per_block * len(blocks)
    assert pushed == sum(payload.nbytes for _, payload, _, _ in sent)
    for b, block in enumerate(blocks):
        for t, (name, table) in enumerate(tables):
            got_table, payload, delta, ids = sent[b * per_block + t]
            want_ids = block["input_rows" if name.startswith("ie")
                             else "output_rows"]
            assert got_table is table
            np.testing.assert_array_equal(ids, want_ids)
            want = trained[b][name][: len(ids)] - replies[b][name]
            assert delta.dtype == np.float32 and delta.shape == want.shape
            np.testing.assert_array_equal(delta, want)
            # a rung with a row to spare: the view cut the rest
            assert trained[b][name].shape[0] \
                == communicator.training_rows(len(ids)) > len(ids)
            assert not payload.flags.writeable
            np.testing.assert_array_equal(payload, delta)


#: a block's row count against the ladder: the foot of the rung 320 (256
#: is a rung itself, so its training rung is the next), its middle and
#: its top less one
COUNTS = {"a_rungs_foot": 256, "its_middle": 288, "its_top_less_one": 319}
RUNG = 320


@pytest.fixture
def comm(request):
    """The communicator alone in a one-worker world, AdaGrad by the
    parameter."""
    import multiverso_tpu as mv
    mv.MV_Init([])
    try:
        yield communicator.Communicator(
            Option(embedding_size=DIM, use_adagrad=request.param,
                   seed=SEED), VOCAB)
    finally:
        mv.MV_ShutDown()


@pytest.mark.parametrize("comm", [True, False], ids=["adagrad", "sgd"],
                         indirect=True)
@pytest.mark.parametrize("crossing", ["whole", "in_pieces"])
@pytest.mark.parametrize("case", COUNTS)
def test_training_copy_and_delta_by_row_count(monkeypatch, comm, crossing,
                                              case):
    """A reply of ``n`` rows: the training copy is ``np.pad``'s to the
    bit and a buffer of its own beside the original, which takes the
    reply's place in ``fetched``; after a "training" that also writes the
    spare rows and the trash row, the delta sent is the host's
    ``trained[:n] - fetched`` to the bit and ``n`` rows long. Whether the
    rows cross whole, padded by the host, or in eighths of the rung; and
    no count builds a program the rung's first count did not."""
    n = COUNTS[case]
    assert communicator.training_rows(n) == RUNG
    monkeypatch.setattr(matrix_table, "_HOST_CUT_PAD_BYTES",
                        0 if crossing == "in_pieces" else 4 << 20)
    tables = _tables_of(comm)
    rng = np.random.default_rng(n)
    # each a view of a longer array, as a reply is of its bucket
    fetched = {name: rng.standard_normal((RUNG, DIM)).astype(np.float32)[:n]
               for name, _ in tables}
    replies = dict(fetched)
    kept = {name: rows.copy() for name, rows in fetched.items()}
    names = ("table.add.host_pieces", "we.host_plane.fetched_bytes")
    before = [_counter(name) for name in names]
    pads = matrix_table._pad_row_batch._cache_size()
    state = comm.training_state(fetched)
    pieces, counted = (_counter(name) - was
                       for name, was in zip(names, before))
    assert pieces == (0 if crossing == "whole"
                      else len(tables) * -(-8 * n // RUNG))
    assert counted == 0             # wait_rows counts a reply, once
    assert matrix_table._pad_row_batch._cache_size() == pads
    assert set(fetched) == {name for name, _ in tables}
    for name, _ in tables:
        want = np.pad(kept[name], ((0, RUNG - n), (0, 0)))
        copy, original = getattr(state, name), fetched[name]
        assert isinstance(original, jax.Array)
        np.testing.assert_array_equal(np.asarray(copy), want)
        np.testing.assert_array_equal(np.asarray(original), want)
        assert copy is not original
        assert (copy.unsafe_buffer_pointer()
                != original.unsafe_buffer_pointer())
        # nor the reply's memory, which nothing wrote to
        np.testing.assert_array_equal(replies[name], kept[name])
    assert (state.ie_g2 is None) == (not comm.opt.use_adagrad)
    # "training": every row moves, the spare rows and the trash row too
    trained = jax.tree.map(lambda rows: rows * 1.5 + 0.25, state)
    sent = []
    monkeypatch.setattr(
        matrix_table.MatrixWorkerTable, "AddFireForget",
        lambda self, deltas, row_ids=None, option=None:
        sent.append((self, deltas, row_ids)))
    ids = np.arange(n, dtype=np.int32)
    originals = dict(fetched)
    pushed = _counter("we.host_plane.pushed_bytes")
    comm.add_delta_parameter(trained, fetched, ids, ids)
    assert _counter("we.host_plane.pushed_bytes") - pushed \
        == len(tables) * n * DIM * 4
    assert [table for table, _, _ in sent] == [table for _, table in tables]
    for (name, _), (_, delta, got_ids) in zip(tables, sent):
        assert got_ids is ids
        assert delta.shape == (n, DIM) and delta.dtype == np.float32
        assert not delta.flags.writeable
        np.testing.assert_array_equal(
            delta, np.asarray(getattr(trained, name))[:n] - kept[name])
        # consumed: the delta was written where the original was
        assert originals[name].is_deleted()
    assert not fetched
    # one program a rung, whatever the count: another count of the rung
    # builds neither program again
    programs = (communicator._training_copy, communicator._rung_delta)
    built = [program._cache_size() for program in programs]
    other = 256 + (n + 17) % 64
    assert other != n and communicator.training_rows(other) == RUNG
    fetched = {name: np.ones((other, DIM), np.float32) for name, _ in tables}
    ids = np.arange(other, dtype=np.int32)
    comm.add_delta_parameter(comm.training_state(fetched), fetched, ids, ids)
    assert [program._cache_size() for program in programs] == built
    assert all(delta.shape == (other, DIM) and not delta.any()
               for _, delta, _ in sent[len(tables):])


@pytest.mark.parametrize("crossing", ["whole", "in_pieces"])
@pytest.mark.parametrize("ids", ["distinct", "repeated"])
def test_an_add_never_writes_to_its_payload(monkeypatch, crossing, ids):
    """What the push hands ``AddFireForget`` is a view of a device
    array's host copy, which numpy holds read-only: an Add of such a
    payload lands as a writable one's does, whether it crosses whole or
    in pieces and whether ``_combine_duplicate_rows`` sums repeated ids
    (a block's never repeat; a caller's may)."""
    import multiverso_tpu as mv
    from multiverso_tpu.tables import MatrixTableOption
    monkeypatch.setattr(matrix_table, "_HOST_CUT_PAD_BYTES",
                        0 if crossing == "in_pieces" else 4 << 20)
    n = 300
    rng = np.random.default_rng(n)
    row_ids = (rng.permutation(VOCAB)[:n] if ids == "distinct"
               else rng.integers(0, 40, n)).astype(np.int32)
    payload = np.asarray(jax.numpy.asarray(
        rng.standard_normal((RUNG, DIM)).astype(np.float32)))[:n]
    assert not payload.flags.writeable
    kept = payload.copy()
    mv.MV_Init([])
    try:
        table = mv.MV_CreateTable(MatrixTableOption(num_rows=VOCAB,
                                                    num_cols=DIM))
        table.AddFireForget(payload, row_ids=row_ids)
        got = np.array(table.GetRows(np.arange(VOCAB, dtype=np.int32)))
    finally:
        mv.MV_ShutDown()
    want = np.zeros((VOCAB, DIM), np.float32)
    np.add.at(want, row_ids, kept)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(payload, kept)


class _Rows:
    """What ``_leaving_rows`` reads of a device array."""

    def __init__(self, bucket, cols=128):
        self.shape, self.nbytes = (bucket, cols), bucket * cols * 4


LEAVING = {
    # bucket, rows asked for, the side that drops the pad, rows crossing
    "under_the_constant": (10_240, 10_000, "host", 10_240),
    "over_it_and_a_fifth_of_the_rows": (1_048_576, 900_000, "host",
                                        1_048_576),
    "over_it_and_a_quarter_of_the_rows": (81_920, 65_537, "host", 81_920),
    "over_it_and_half_the_rows": (1_048_576, 699_051, "device", 786_432),
    "over_it_and_a_callers_bucket": (1_048_576, 70_000, "device", 131_072),
    "an_odd_bucket": (100_001, 50_000, "device", 50_004),
}


@pytest.mark.parametrize("case", LEAVING)
def test_leaving_rows_by_bucket_and_count(monkeypatch, case):
    bucket, n, side, crossing = LEAVING[case]
    cut = []
    monkeypatch.setattr(matrix_table, "_cut_rows",
                        lambda rows, m: cut.append(m) or _Rows(m))
    names = ("table.get.host_cuts", "table.get.device_cuts")
    before = [_counter(name) for name in names]
    left = matrix_table._leaving_rows(_Rows(bucket), n)
    stepped = [_counter(name) - was for name, was in zip(names, before)]
    assert left.shape[0] == crossing >= n
    assert stepped == [int(side == "host"), int(side == "device")]
    assert cut == ([crossing] if side == "device" else [])
    # the cut's shape is one of the bucket's eighths, whatever the count
    if side == "device":
        others = {matrix_table._leaving_rows(_Rows(bucket), m).shape[0]
                  for m in range(n - 40, n)}
        assert others == {crossing}


PLACED = {
    # rows, columns, bucket -> pieces crossed (0: exact-size, padded by
    # the device's pad program, the one launch it took before)
    "small_verb": (10_000, 50, 10_240, 0),
    "pad_at_the_constant": (32_768, 128, 40_960, 0),
    "pad_over_it_8_pieces": (36_000, 256, 40_960, 8),
    "pad_over_it_7_pieces": (32_000, 128, 40_960, 7),
    "a_callers_bucket_3_pieces": (12_000, 128, 40_960, 3),
    "shorter_than_a_piece": (5_000, 128, 40_960, 0),
}


@pytest.mark.parametrize("case", PLACED)
def test_a_host_delta_crosses_whole_or_in_pieces(monkeypatch, case):
    """Over the constant a host delta crosses in eighths of its bucket,
    views of the sender's array, and the joined pieces are
    ``_pad_row_batch``'s result bit for bit; under it the verb takes the
    branch, and the launch, it took."""
    n, cols, bucket, pieces = PLACED[case]
    # the cases' sizes, a sixteenth the bytes: the constant with them
    monkeypatch.setattr(matrix_table, "_HOST_CUT_PAD_BYTES", (4 << 20) // 16)
    n, bucket = n // 16, bucket // 16
    rng = np.random.default_rng(n)
    deltas = rng.standard_normal((n, cols)).astype(np.float32)
    placed = []
    inner = matrix_table.crossing.place

    def place(host, put=None):
        placed.append(host)
        return inner(host, put) if put is not None else inner(host)
    monkeypatch.setattr(matrix_table.crossing, "place", place)
    names = ("table.device.calls", "table.device.h2d_copies",
             "table.add.host_pieces")
    before = [_counter(name) for name in names]
    pads = matrix_table._pad_row_batch._cache_size()
    out = matrix_table._place_rows(deltas, bucket)
    stepped = [_counter(name) - was for name, was in zip(names, before)]
    want = np.zeros((bucket, cols), np.float32)
    want[:n] = deltas
    np.testing.assert_array_equal(np.asarray(out), want)
    if not pieces:
        assert placed == [deltas] and placed[0] is deltas
        assert stepped == [1, 1, 0]         # the pad program's launch
        assert matrix_table._pad_row_batch._cache_size() == pads + 1
        return
    (crossed,) = placed
    assert len(crossed) == pieces and stepped == [1, pieces, pieces]
    assert {p.shape for p in crossed} == {(bucket // 8, cols)}
    assert all(p.base is deltas for p in crossed)       # views: no copy
    assert matrix_table._pad_row_batch._cache_size() == pads
    # another row count of the same piece count: the same program
    joins = matrix_table._join_row_pieces._cache_size()
    fewer = deltas[: n - 3]
    out = matrix_table._place_rows(fewer, bucket)
    want[n - 3: n] = 0
    np.testing.assert_array_equal(np.asarray(out), want)
    assert matrix_table._join_row_pieces._cache_size() == joins


def test_the_two_copies_of_the_reference_are_one():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "benchmark", "reference",
                           "sgns_adagrad_pipeline.py"), "rb") as f:
        bench = f.read()
    with open(ref.__file__, "rb") as f:
        assert f.read() == bench
