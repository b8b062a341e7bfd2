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
* the gauges and counters the objective brought step as said.
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
        step = jax.jit(device_pairs._make_sparse_adagrad_step())
        trash = 1
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


def _train_a_pass(tmp_path, monkeypatch, threshold: int):
    """The app's own prepare() / train() on the corpus; -> (trainer, the
    blocks it trained as (tokens, sentence numbers), average loss)."""
    from multiverso_tpu.models.wordembedding import device_pairs
    from multiverso_tpu.models.wordembedding.distributed import (
        DistributedWordEmbedding)
    monkeypatch.setattr(device_pairs, "_SPARSE_BYTES", threshold)
    corpus = tmp_path / "corpus.txt"
    _corpus(str(corpus))
    opt = Option(train_file=str(corpus), output_file=str(tmp_path / "v.txt"),
                 embedding_size=DIM, window_size=1, negative_num=0,
                 min_count=1, epoch=1, data_block_size=2400,
                 pair_batch_size=64, init_learning_rate=0.025,
                 use_adagrad=True, cbow=True, hs=True, device_pairs=True,
                 is_pipeline=False, seed=11)
    we = DistributedWordEmbedding(opt)
    we.prepare()
    blocks, inner = [], we._train_block

    def keeping(block, step):
        blocks.append((block.tokens, block.token_sent))
        return inner(block, step)
    we._train_block = keeping
    return we, blocks, we.train()


@pytest.mark.parametrize("step_kind,threshold",
                         [("dense", 1 << 60), ("touched_rows", 0)])
def test_a_whole_pass_at_window_1_leaves_the_references_tables(
        tmp_path, monkeypatch, step_kind, threshold):
    we, blocks, loss = _train_a_pass(tmp_path, monkeypatch, threshold)
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
