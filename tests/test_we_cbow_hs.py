"""The WordEmbedding app's other objective, CBOW with hierarchical softmax
(``-cbow 1 -hs 1``), against its plain reference
(``multiverso_tpu/models/wordembedding/cbow_hs_reference.py``: the tree by
the textbook heap, the step in plain float32 ``jax.numpy``):

* the array-built Huffman tree equals the heap's, code for code and point
  for point, ties included; it is prefix-free and of least expected length;
* one batch step of ``make_train_step`` and of the touched-rows
  ``_make_sparse_adagrad_step`` on CBOW + HS lanes, and on the two mixed
  objectives beside it, gives the reference's rows and accumulators;
* a whole pass of the fused ``-device_pairs 1`` program at ``-window 1``,
  where the shrunk window has one value and nothing in an HS pass is
  random, leaves the reference's four tables, by the dense step and by
  the touched-rows step;
* the gauges and counters the objective brought step as said;
* the touched-rows step's update, which walks a batch's distinct rows in
  chunks of one batch's pairs, leaves the live rows of the update of every
  lane to the bit, counts the lanes it ran, and traces one row kernel a
  write and one loop a looped table.
"""

import heapq
import os

import numpy as np
import pytest

from multiverso_tpu.models.wordembedding import cbow_hs_reference as ref
from multiverso_tpu.models.wordembedding.huffman import HuffmanEncoder
from multiverso_tpu.models.wordembedding.option import Option

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_the_reference_is_kept_twice_byte_for_byte():
    twin = os.path.join(REPO, "benchmark", "reference", "cbow_hs_adagrad.py")
    if not os.path.exists(twin):
        pytest.skip("no benchmark beside the package")
    with open(twin, "rb") as a, open(ref.__file__, "rb") as b:
        assert a.read() == b.read()


# -- the tree ------------------------------------------------------------------

def _counts(vocab: int, seed: int) -> np.ndarray:
    """Descending counts with many ties (a dictionary's order), the rarest
    words all equal."""
    rng = np.random.default_rng(seed)
    counts = np.sort(rng.integers(1, max(4, vocab // 3), vocab))[::-1].copy()
    counts[vocab // 2:] = counts[vocab // 2]
    return counts


@pytest.mark.parametrize("vocab", [2, 3, 100, 5000])
def test_the_array_built_tree_is_the_heaps(vocab):
    counts = _counts(vocab, seed=vocab)
    enc = HuffmanEncoder()
    enc.BuildFromTermFrequency(counts.tolist())
    points, codes, lengths = ref.paths(ref.huffman_tree(counts),
                                       np.arange(vocab))
    assert enc.VocabSize() == vocab
    assert enc.max_code_length == points.shape[1] == lengths.max()
    assert np.array_equal(enc.lengths, lengths)
    assert np.array_equal(enc.points, points)
    assert np.array_equal(enc.codes, codes)
    for w in (0, vocab // 2, vocab - 1):
        info = enc.GetLabelInfo(w)
        assert info.points == points[w, :lengths[w]].tolist()
        assert info.codes == codes[w, :lengths[w]].tolist()
    # an output row is an inner node; row V - 1 is none
    on_path = np.arange(points.shape[1])[None, :] < lengths[:, None]
    assert enc.points[on_path].max() == vocab - 2 and enc.points.min() == 0
    assert not enc.points[~on_path].any() and not enc.codes[~on_path].any()
    # prefix-free: distinct codes, and a full binary tree (Kraft's sum is 1)
    words = {tuple(enc.codes[w, :lengths[w]].tolist()) for w in range(vocab)}
    assert len(words) == vocab
    assert sum(2.0 ** -int(n) for n in lengths) == 1.0
    # of least expected length: the sum of the merges' counts
    heap, cost = counts.tolist(), 0
    heapq.heapify(heap)
    while len(heap) > 1:
        merged = heapq.heappop(heap) + heapq.heappop(heap)
        cost += merged
        heapq.heappush(heap, merged)
    assert int((counts * lengths).sum()) == cost


def test_one_word_has_no_path():
    enc = HuffmanEncoder()
    enc.BuildFromTermFrequency([7])
    assert enc.max_code_length == 0 and enc.VocabSize() == 1
    assert enc.GetLabelInfo(0).codes == [] == enc.GetLabelInfo(0).points


# -- one batch step ------------------------------------------------------------

ROWS, DIM, LANES = 60, 16, 48


def _lanes(objective: str, rng) -> tuple:
    """(inputs, imask, outputs, labels, omask) of one lane-batch: ids
    repeat within the batch (the root is on every path), masks have
    holes."""
    cbow, hs = objective.startswith("cbow"), objective.endswith("hs")
    cin, cout = (6 if cbow else 1), (9 if hs else 4)
    inputs = rng.integers(0, ROWS, (LANES, cin)).astype(np.int32)
    imask = (rng.random((LANES, cin)) < 0.6).astype(np.float32)
    imask[:, 0] = 1.0
    outputs = rng.integers(0, ROWS - 1, (LANES, cout)).astype(np.int32)
    if hs:
        outputs[:, 0] = ROWS - 2                    # the root
        lengths = rng.integers(1, cout + 1, LANES)
        omask = (np.arange(cout)[None, :] < lengths[:, None]).astype(
            np.float32)
        labels = rng.integers(0, 2, (LANES, cout)).astype(np.float32)
    else:
        omask = (rng.random((LANES, cout)) < 0.9).astype(np.float32)
        labels = np.zeros((LANES, cout), np.float32)
        labels[:, 0] = 1.0
    omask[-3:] = 0.0                                # centres with no context
    imask[-3:] = 0.0
    return inputs, imask, outputs * (omask > 0), labels, omask


@pytest.mark.parametrize("step_kind", ["dense", "touched_rows"])
@pytest.mark.parametrize("objective", ["cbow_hs", "skipgram_hs", "cbow_neg"])
def test_a_batch_step_gives_the_references_rows(objective, step_kind):
    import jax.numpy as jnp
    from multiverso_tpu.models.wordembedding import device_pairs
    from multiverso_tpu.models.wordembedding.model import (TrainState,
                                                           make_train_step)
    rng = np.random.default_rng(len(objective))
    tables = [rng.standard_normal((ROWS, DIM)).astype(np.float32) * 0.1
              for _ in range(2)]
    tables += [np.abs(rng.standard_normal((ROWS, DIM))).astype(np.float32)
               * 1e-3 for _ in range(2)]
    batches = [_lanes(objective, rng) for _ in range(2)]
    want = tuple(jnp.asarray(t) for t in tables)
    plain = ref.make_step()
    for batch in batches:
        want, want_loss = plain(want, *map(jnp.asarray, batch),
                                jnp.float32(0.05))
    if step_kind == "dense":
        step, trash = make_train_step(True), 0
    else:       # over full storage: a trash row at the end, ids as they are
        import jax
        sparse = jax.jit(device_pairs._make_sparse_adagrad_step())
        step, trash = (lambda *args: sparse(*args)[:2]), 1
    got = TrainState(*(jnp.asarray(np.concatenate(
        [t, np.zeros((trash, DIM), np.float32)])) for t in tables))
    for batch in batches:
        got, got_loss = step(got, *map(jnp.asarray, batch),
                             jnp.float32(0.05))
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    for name, a, b in zip(("ie", "eo", "ie_g2", "eo_g2"), got, want):
        np.testing.assert_allclose(np.asarray(a)[:ROWS], np.asarray(b),
                                   rtol=0, atol=1e-6, err_msg=name)


# -- a whole pass at -window 1 -------------------------------------------------

VOCAB, TOPIC = 60, 6


def _corpus(path, sentences=160, seed=3):
    """Sentences of 2 to 9 words of one topic of 6, and a few of one word,
    whose word is no example."""
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for i in range(sentences):
            topic = rng.integers(VOCAB // TOPIC)
            n = 1 if i % 40 == 7 else rng.integers(2, 10)
            f.write(" ".join(f"w{topic * TOPIC + min(rng.geometric(0.4) - 1, TOPIC - 1)}"
                             for _ in range(n)) + "\n")


def _train_a_pass(tmp_path, monkeypatch, threshold: int, batch: int = 64,
                  one_device: bool = False):
    """The app's own prepare() / train() on the corpus; -> (trainer, the
    blocks it trained as (tokens, sentence numbers), average loss).
    ``one_device`` holds the world to one of the eight: over more the
    touched-rows step is the per-shard one, which updates every lane."""
    from multiverso_tpu.models.wordembedding import device_pairs
    from multiverso_tpu.models.wordembedding.distributed import (
        DistributedWordEmbedding)
    monkeypatch.setattr(device_pairs, "_SPARSE_BYTES", threshold)
    if one_device:
        import jax
        import multiverso_tpu as mv
        mv.MV_Init([], devices=jax.devices()[:1])
    corpus = tmp_path / "corpus.txt"
    _corpus(str(corpus))
    opt = Option(train_file=str(corpus), output_file=str(tmp_path / "v.txt"),
                 embedding_size=DIM, window_size=1, negative_num=0,
                 min_count=1, epoch=1, data_block_size=2400,
                 pair_batch_size=batch, init_learning_rate=0.025,
                 use_adagrad=True, cbow=True, hs=True, device_pairs=True,
                 is_pipeline=False, seed=11)
    we = DistributedWordEmbedding(opt)
    we._world.owns = one_device     # close() ends the world made above
    we.prepare()
    blocks, inner = [], we._train_block

    def keeping(block, step):
        blocks.append((block.tokens, block.token_sent))
        return inner(block, step)
    we._train_block = keeping
    return we, blocks, we.train()


@pytest.mark.parametrize("step_kind,threshold", [
    ("dense", 1 << 60), ("touched_rows", 0), ("touched_rows_one_device", 0)])
def test_a_whole_pass_at_window_1_leaves_the_references_tables(
        tmp_path, monkeypatch, step_kind, threshold):
    we, blocks, loss = _train_a_pass(
        tmp_path, monkeypatch, threshold,
        one_device=step_kind.endswith("one_device"))
    try:
        opt, comm = we.opt, we.comm
        assert len(blocks) >= 3
        vocab = we.dictionary.Size()
        want = ref.train_pass(
            blocks, we.dictionary.counts(), DIM, opt.seed,
            opt.init_learning_rate, opt.window_size, opt.pair_batch_size,
            np.random.default_rng(0))       # one window value: never read
        tokens = sum(len(ids) for ids, _ in blocks)
        assert want["examples"] == we.total_pairs < tokens
        assert loss == pytest.approx(want["loss"] / want["examples"],
                                     rel=1e-5)
        every = np.arange(vocab, dtype=np.int32)
        got = {"ie": comm.input_table, "eo": comm.output_table,
               "ie_g2": comm.ie_g2_table, "eo_g2": comm.eo_g2_table}
        got = {k: np.array(t.GetRows(every)) for k, t in got.items()}
        # stated tolerance: 1e-5 absolute on rows of size 1e-2 to 1e-1
        # (two orders of summation in float32 over some thirty steps)
        for side, ids in (("ie", want["in_ids"]), ("eo", want["out_ids"])):
            for name in (side, side + "_g2"):
                np.testing.assert_allclose(got[name][ids], want[name],
                                           rtol=0, atol=1e-5, err_msg=name)
            # and nothing else moved: the other words keep their initial
            # rows, the other nodes (row V - 1, no node, among them) zeros
            rest = np.setdiff1d(every, ids)
            start = (ref.init_input(vocab, DIM, opt.seed) if side == "ie"
                     else np.zeros((vocab, DIM), np.float32))
            assert np.array_equal(got[side][rest], start[rest])
            assert not got[side + "_g2"][rest].any()
        assert vocab - 1 not in want["out_ids"]
    finally:
        we.close()


# -- gauges and counters -------------------------------------------------------

def test_the_gauges_and_counters_step_as_said(tmp_path, monkeypatch):
    from multiverso_tpu.telemetry import metrics
    before = metrics.snapshot()     # counters outlive a world
    we, blocks, _ = _train_a_pass(tmp_path, monkeypatch, 0)
    try:
        snap = metrics.snapshot()
        enc = we.huffman

        def value(name):
            was = before.get(name, {"value": 0})["value"]
            return snap[name]["value"] - (
                was if snap[name]["type"] == "counter" else 0)
        assert value("we.prepare.huffman_s") > 0
        assert value("we.hs.max_code") == enc.max_code_length
        vocab, words = enc.VocabSize(), -(-enc.max_code_length // 32)
        assert value("we.hs.table_bytes") == (
            4 * vocab * enc.max_code_length + 4 * vocab * words + 4 * vocab)
        tokens = np.concatenate([ids for ids, _ in blocks])
        assert value("we.hs.path_lanes.valid") == enc.lengths[tokens].sum()
        assert value("we.hs.path_lanes.padded") == (
            len(tokens) * enc.max_code_length)
        assert value("we.cbow.centres") == we.total_pairs
    finally:
        we.close()


# -- the update walks the batch's distinct rows in chunks -----------------------

def full_lane_step(eps: float = 1e-10):
    """The touched-rows step with the update it had before the loop: every
    lane ``dedup_rows`` returns is gathered, updated and written, the pad
    lanes to the trash row. What the looped update is held to, bit for
    bit on every row but that one."""
    import jax
    import jax.numpy as jnp
    from multiverso_tpu import ops
    from multiverso_tpu.models.wordembedding.model import TrainState

    def step(state, inputs, imask, outputs, labels, omask, lr):
        ie, eo = state.ie, state.eo
        D = ie.shape[1]
        in_rows = ops.gather_rows(ie, inputs.reshape(-1)).reshape(
            inputs.shape + (D,))
        denom = jnp.maximum(imask.sum(axis=1, keepdims=True), 1.0)
        h = (in_rows * imask[:, :, None]).sum(axis=1) / denom
        out_rows = ops.gather_rows(eo, outputs.reshape(-1)).reshape(
            outputs.shape + (D,))
        f = jax.nn.sigmoid(jnp.einsum("pd,pcd->pc", h, out_rows))
        err = (labels - f) * omask
        loss = -jnp.sum(omask * (labels * jnp.log(f + 1e-7) +
                                 (1 - labels) * jnp.log(1 - f + 1e-7)))
        hid_err = jnp.einsum("pc,pcd->pd", err, out_rows)

        def row_update(tab, g2tab, ids, contrib):
            uids, grads = ops.dedup_rows(ids.reshape(-1),
                                         contrib.reshape(-1, D))
            uids = jnp.where(uids < 0, tab.shape[0] - 1, uids)
            g2_rows = ops.gather_rows(g2tab, uids) + grads * grads
            rows = ops.gather_rows(tab, uids) + jnp.where(
                g2_rows > eps, lr * grads / jnp.sqrt(g2_rows + 1e-12), 0.0)
            return (ops.scatter_set_rows(tab, uids, rows),
                    ops.scatter_set_rows(g2tab, uids, g2_rows))

        eo, eo_g2 = row_update(eo, state.eo_g2, outputs,
                               err[:, :, None] * h[:, None, :])
        ie, ie_g2 = row_update(ie, state.ie_g2, inputs,
                               hid_err[:, None, :] * imask[:, :, None])
        return TrainState(ie, eo, ie_g2, eo_g2), loss
    return step


STORE, PAIRS = 700, 32      # rows of a table less its trash row; a batch


def _ids_of(distinct: int, shape, rng) -> np.ndarray:
    """``shape`` lanes over exactly ``distinct`` rows of the store."""
    rows = rng.choice(STORE, distinct, replace=False)
    n = int(np.prod(shape))
    return rng.permutation(np.resize(rows, n)).reshape(shape).astype(np.int32)


def _batch_of(in_width, out_width, k_in, k_out, rng) -> tuple:
    inputs = _ids_of(k_in, (PAIRS, in_width), rng)
    outputs = _ids_of(k_out, (PAIRS, out_width), rng)
    imask = (rng.random(inputs.shape) < 0.8).astype(np.float32)
    imask[:, 0] = 1.0
    omask = (rng.random(outputs.shape) < 0.8).astype(np.float32)
    labels = rng.integers(0, 2, outputs.shape).astype(np.float32)
    return inputs, imask, outputs, labels, omask


def _lanes_run(ids: np.ndarray) -> int:
    """The lanes a table's update runs: whole chunks of one batch's pairs
    over the distinct ids; a lane a pair runs as laid out."""
    chunk = ids.shape[0]
    if ids.size == chunk:
        return chunk
    return -(-len(np.unique(ids)) // chunk) * chunk


def _run_both(batches, use_pallas="auto"):
    """-> (looped, full lane) after ``batches`` in turn from the same four
    tables: ([ie, eo, ie_g2, eo_g2] with their trash row, the last loss,
    the lanes each looped step ran)."""
    import jax
    import jax.numpy as jnp
    from multiverso_tpu.models.wordembedding import device_pairs
    from multiverso_tpu.models.wordembedding.model import TrainState
    from multiverso_tpu.utils.configure import SetCMDFlag
    rng = np.random.default_rng(7)
    tables = [rng.standard_normal((STORE + 1, 128)).astype(np.float32) * 0.1
              for _ in range(2)]
    tables += [np.abs(rng.standard_normal((STORE + 1, 128))).astype(
        np.float32) * 1e-3 for _ in range(2)]
    SetCMDFlag("use_pallas", use_pallas)
    try:
        looped = jax.jit(device_pairs._make_sparse_adagrad_step())
        full = jax.jit(full_lane_step())
        got = want = TrainState(*map(jnp.asarray, tables))
        ran = []
        for batch in batches:
            batch = tuple(map(jnp.asarray, batch))
            got, got_loss, lanes = looped(got, *batch, jnp.float32(0.05))
            want, want_loss = full(want, *batch, jnp.float32(0.05))
            ran.append(int(lanes))
    finally:
        SetCMDFlag("use_pallas", "auto")
    return (([np.asarray(t) for t in got], float(got_loss), ran),
            ([np.asarray(t) for t in want], float(want_loss)))


def _assert_live_rows_equal(got, want):
    (got, got_loss, _), (want, want_loss) = got, want
    assert got_loss == want_loss
    for name, a, b in zip(("ie", "eo", "ie_g2", "eo_g2"), got, want):
        assert np.array_equal(a[:STORE], b[:STORE]), name


@pytest.mark.parametrize("use_pallas", ["auto", "on"])
@pytest.mark.parametrize("lanes", [
    # CBOW + HS: ten context lanes and a path of 27 a pair, both looped
    ("cbow_hs", 10, 27, 100, 250),
    # skip-gram, negative sampling: a lane a pair in (no loop), six out
    ("skipgram_neg", 1, 6, 20, 70),
], ids=lambda lanes: lanes[0])
def test_the_looped_update_is_the_update_of_every_lane(lanes, use_pallas):
    """Three batches in turn, the AdaGrad accumulators carried: every row
    of the four tables but the trash row to the bit, the loss the same
    float, and the lanes run a numpy count over the distinct ids."""
    _, in_width, out_width, k_in, k_out = lanes
    rng = np.random.default_rng(in_width)
    batches = [_batch_of(in_width, out_width, k_in + 9 * i, k_out + 40 * i,
                         rng) for i in range(3)]
    got, want = _run_both(batches, use_pallas)
    _assert_live_rows_equal(got, want)
    assert got[2] == [_lanes_run(b[0]) + _lanes_run(b[2]) for b in batches]
    laid_out = PAIRS * (in_width + out_width)
    assert all(0 < ran <= laid_out for ran in got[2])
    if in_width > 1:
        assert max(got[2]) < laid_out


@pytest.mark.parametrize("k_in,k_out,chunks", [
    (1, 1, (1, 1)),                 # one distinct row a table: one chunk
    (PAIRS, 3 * PAIRS, (1, 3)),     # whole chunks: no chunk past the last
    (PAIRS + 1, 3 * PAIRS + 1, (2, 4)),     # one live lane in the last
    (4 * PAIRS, 8 * PAIRS, (4, 8)),         # every lane a distinct row
])
def test_the_last_chunk_is_the_last_that_holds_a_row(k_in, k_out, chunks):
    """Four input lanes and eight output lanes a pair: the count of
    distinct rows at a chunk's edge, one row, and no lane shared."""
    batch = _batch_of(4, 8, k_in, k_out, np.random.default_rng(k_out))
    assert len(np.unique(batch[0])) == k_in
    assert len(np.unique(batch[2])) == k_out
    got, want = _run_both([batch])
    _assert_live_rows_equal(got, want)
    assert got[2] == [PAIRS * sum(chunks)]


# we_cbow_hs' and we_pairs' lanes a step: (inputs, outputs) a pair of 8,192
CELL_LANES = {"we_cbow_hs": (10, 27), "we_pairs": (1, 6)}


def _walk(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs (a loop's body,
    a jitted call, a kernel's) after the equation that holds them."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub)


@pytest.mark.parametrize("cell,looped_tables", [("we_cbow_hs", 2),
                                                ("we_pairs", 1)])
def test_the_step_traces_one_kernel_a_write_and_one_loop_a_table(
        cell, looped_tables):
    """What a cell's set-up pays to trace, at the cell's own shapes under
    ``-use_pallas=on``: four ``pallas_call`` equations (two a table, what
    skip-gram's step always held) and one ``while`` a table whose update
    is looped, every kernel call on ids of one batch's pairs. A ladder of
    branches, each with its own writes, fails here and not in a driver's
    ``setup_s`` (PERF.md section 6, PR 49)."""
    import jax
    import jax.numpy as jnp
    from multiverso_tpu.models.wordembedding import device_pairs
    from multiverso_tpu.models.wordembedding.model import TrainState
    from multiverso_tpu.utils.configure import SetCMDFlag
    in_width, out_width = CELL_LANES[cell]
    table = jax.ShapeDtypeStruct((2_097_101, 128), jnp.float32)
    s = lambda width, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        (8_192, width), dtype)
    SetCMDFlag("use_pallas", "on")
    try:
        jaxpr = jax.make_jaxpr(device_pairs._make_sparse_adagrad_step())(
            TrainState(table, table, table, table), s(in_width, jnp.int32),
            s(in_width, jnp.float32), s(out_width, jnp.int32),
            s(out_width, jnp.float32), s(out_width, jnp.float32),
            jax.ShapeDtypeStruct((), jnp.float32))
    finally:
        SetCMDFlag("use_pallas", "auto")
    eqns = list(_walk(jaxpr.jaxpr))
    calls = [eqn for eqn in eqns if eqn.primitive.name == "pallas_call"]
    assert len(calls) == 4
    # a kernel's body holds no loop: the whiles are the step's own
    assert sum(eqn.primitive.name == "while"
               for eqn in eqns) == looped_tables
    # one shape of the kernel: every write is of one batch's pairs
    assert {tuple(v.aval.shape) for eqn in calls for v in eqn.invars} == {
        (2_097_101, 128), (8_192,), (8_192, 128)}


def test_the_counters_of_the_updates_lanes_are_a_numpy_count(tmp_path,
                                                             monkeypatch):
    """A whole pass at ``-window 1`` (nothing random) in batches of 16
    centres over 60 words: ``we.update.lanes.run``, which rides in the
    block's stats array, is the count below over the batches that hold an
    example, and ``.laid_out`` every lane of theirs."""
    from multiverso_tpu.parallel.mesh import next_bucket
    from multiverso_tpu.telemetry import metrics
    names = ("we.update.lanes.run", "we.update.lanes.laid_out",
             "we.block.steps.run")
    before = [metrics.counter(n).value for n in names]
    we, blocks, _ = _train_a_pass(tmp_path, monkeypatch, 0, batch=16,
                                  one_device=True)
    try:
        run, laid_out, steps = (metrics.counter(n).value - was
                                for n, was in zip(names, before))
        points, longest = we.huffman.points, we.huffman.max_code_length
        want_run = want_steps = 0
        for ids, sent in blocks:
            words, live = ref.contexts(ids, sent, 1, np.random.default_rng(0))
            pad = next_bucket(len(ids), min_bucket=1024) - len(ids)
            words = np.pad(words * live, ((0, pad), (0, 0)))
            centres = np.pad(ids * live.any(axis=1), (0, pad))
            example = np.pad(live.any(axis=1), (0, pad))
            for at in range(0, len(centres), 16):
                if example[at:at + 16].any():
                    want_steps += 1
                    want_run += (_lanes_run(words[at:at + 16])
                                 + _lanes_run(points[centres[at:at + 16]]))
        assert steps == want_steps > 20
        assert run == want_run
        assert laid_out == want_steps * 16 * (2 + longest)
        assert we._dp_trainer.step_update_lanes == 16 * (2 + longest)
        assert run < laid_out
    finally:
        we.close()
