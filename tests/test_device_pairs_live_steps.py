"""The block program of ``-device_pairs 1`` runs the lane-batches that
hold a pair, not the ``nb`` its buckets lay out
(``models/wordembedding/device_pairs.py`` ``_program``): a batch without
a pair is a step that changes nothing, so the same block laid out in
``nb`` and in ``2 nb`` batches leaves the same tables to the last bit and
runs the same steps, fewer than ``nb``; their count is what numpy counts
from the token stream; a block of one-word sentences runs none. One case
each for the touched-rows AdaGrad step on one shard, the same under
``shard_map`` over four, CBOW with hierarchical softmax, the dense
small-vocabulary step and plain SGD. Then the two counters at the app's
harvest, and the compiled program's loop.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.models.wordembedding import device_pairs as dp
from multiverso_tpu.models.wordembedding.communicator import Communicator
from multiverso_tpu.models.wordembedding.option import Option
from multiverso_tpu.parallel.mesh import next_bucket
from multiverso_tpu.telemetry import metrics

VOCAB, DIM, SEED, LR, T_PAD, WINDOW = 400, 128, 11, 0.025, 1024, 2

#: case -> (shards, the bytes of a table over which the step is the
#: touched-rows one, options, lanes a batch)
CASES = {
    "sparse_adagrad": (1, 0, dict(use_adagrad=True, negative_num=3), 256),
    "four_shards": (4, 0, dict(use_adagrad=True, negative_num=3), 256),
    "cbow_hs": (1, 0, dict(use_adagrad=True, cbow=True, hs=True,
                           negative_num=0), 128),
    "dense_adagrad": (1, 1 << 60, dict(use_adagrad=True, negative_num=3),
                      256),
    "sgd": (1, 1 << 60, dict(use_adagrad=False, negative_num=3), 256),
}


class _World:
    """A world of the case's devices holding the app's tables, and the
    block program's operands."""

    def __init__(self, case, monkeypatch):
        shards, threshold, options, self.batch = CASES[case]
        monkeypatch.setattr(dp, "_SPARSE_BYTES", threshold)
        dp._PROGRAM_CACHE.clear()
        mv.MV_Init(["-num_workers=1"], devices=jax.devices()[:shards])
        self.opt = Option(embedding_size=DIM, window_size=WINDOW,
                          device_pairs=True, pair_batch_size=self.batch,
                          seed=SEED, init_learning_rate=LR, **options)
        self.comm = Communicator(self.opt, VOCAB)
        self.trainer = dp.DevicePairsTrainer(
            self.opt, self.comm, np.arange(VOCAB, 0, -1))
        # lanes the program lays out, and the batches they need
        self.lanes = T_PAD if self.opt.cbow else 2 * WINDOW * T_PAD
        self.nb = next_bucket(-(-self.lanes // self.batch), min_bucket=4)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        mv.MV_ShutDown()
        dp._PROGRAM_CACHE.clear()

    def run(self, nb, ids, sent):
        """The block through ``_program(T_PAD, nb)`` from the tables as
        they were created (the program gets copies to donate) ->
        (tables, loss bits, pairs, steps run)."""
        tr = self.trainer
        pad = lambda a: np.concatenate(  # noqa: E731
            [a, np.full(T_PAD - len(a), -1, np.int32)])
        aux = ((tr._hs_points, tr._hs_bits, tr._hs_len) if self.opt.hs
               else (tr._slots,))
        states = tuple(jnp.copy(s) for s in tr._take_states())
        out, stats = tr._program(T_PAD, nb)(
            states, aux, *tr._put((pad(ids), pad(sent))),
            jax.random.PRNGKey(5), jnp.float32(LR))
        stats = np.asarray(stats)
        assert stats.shape == (4,) and stats.dtype == np.int32
        return [np.asarray(t) for t in out], *(int(v) for v in stats[:3])


def _tokens(sentences=40, length=12):
    """480 tokens in sentences of 12 and one of 1: the layout's second
    half is dead lanes."""
    rng = np.random.default_rng(5)
    ids = rng.choice(VOCAB, sentences * length).astype(np.int32)
    sent = np.repeat(np.arange(sentences, dtype=np.int32), length)
    sent[-1] = sentences        # the last token a sentence of its own
    return ids, sent


def _live_batches(sent, cbow, batch):
    """(fewest, most) lane-batches that can hold a pair, from the
    sentence numbers alone. A centre's shrunk window always reaches its
    nearest neighbours, so the ``|d| = 1`` lanes are certain and the
    farther ones (``|d| <= b``, ``b`` drawn in the program) possible.
    CBOW lays out a lane a centre, live with either neighbour: exact.
    Skip-gram lays out a segment of ``T_PAD`` lanes an offset."""
    t = len(sent)

    def lanes(d):           # token i and token i + d share a sentence
        ok = np.zeros(T_PAD, bool)
        i = np.arange(max(0, -d), min(t, t - d))
        ok[i] = sent[i] == sent[i + d]
        return ok

    offsets = [d for d in range(-WINDOW, WINDOW + 1) if d]
    if cbow:
        sure = maybe = lanes(-1) | lanes(1)
    else:
        maybe = np.concatenate([lanes(d) for d in offsets])
        sure = np.concatenate([lanes(d) if abs(d) == 1
                               else np.zeros(T_PAD, bool) for d in offsets])
    count = lambda m: int(m.reshape(-1, batch).any(axis=1).sum())  # noqa: E731
    return count(sure), count(maybe)


@pytest.mark.parametrize("case", list(CASES))
def test_the_loop_runs_the_batches_that_hold_a_pair(case, monkeypatch):
    ids, sent = _tokens()
    with _World(case, monkeypatch) as w:
        nb = w.nb
        assert nb * w.batch == w.lanes      # the layout fills nb exactly
        start = [np.asarray(s) for s in w.trainer._take_states()]
        tables, loss, pairs, steps = w.run(nb, ids, sent)
        twice = w.run(2 * nb, ids, sent)
        # (i) nb more dead batches: the same tables, loss, pairs, steps
        assert (loss, pairs, steps) == twice[1:]
        assert len(tables) == len(start) == (4 if w.opt.use_adagrad else 2)
        for a, b, was in zip(tables, twice[0], start):
            assert np.array_equal(a, b)
            assert not np.array_equal(a, was)       # and it trained
        assert 0 < steps < nb and pairs > 400
        assert np.int32(loss).view(np.float32) > 0
        # (ii) the steps are the batches that hold a centre with a
        # neighbour
        fewest, most = _live_batches(sent, w.opt.cbow, w.batch)
        assert fewest <= steps <= most < nb
        if w.opt.cbow:
            assert steps == fewest == most == -(-(len(ids) - 1) // w.batch)
        # (iii) sentences of one word: no pair, no step, nothing written
        tables, loss, pairs, steps = w.run(
            nb, ids, np.arange(len(ids), dtype=np.int32))
        assert (np.int32(loss).view(np.float32), pairs, steps) == (0, 0, 0)
        for a, was in zip(tables, start):
            assert np.array_equal(a, was)


# -- the counters at the harvest ------------------------------------------------

def _corpus(path, sentences=160):
    """Sentences of 2 to 9 words, every fortieth of one."""
    rng = np.random.default_rng(3)
    with open(path, "w") as f:
        for i in range(sentences):
            n = 1 if i % 40 == 7 else rng.integers(2, 10)
            f.write(" ".join(f"w{rng.integers(60)}" for _ in range(n))
                    + "\n")


#: plane -> the app's options: ``-device_pairs`` (a CBOW pass, where numpy
#: knows the live batches from the token stream) and the block rounds of
#: the device plane, the host plane and the host plane's block pipeline
PLANES = {
    "device_pairs": dict(negative_num=0, cbow=True, hs=True,
                         device_pairs=True, is_pipeline=False),
    "device_plane": dict(negative_num=3, device_plane=True,
                         is_pipeline=False),
    "host_plane": dict(negative_num=3, is_pipeline=False),
    "host_pipeline": dict(negative_num=3, is_pipeline=True),
}


@pytest.mark.parametrize("plane", list(PLANES))
def test_the_harvest_counts_the_steps_run_of_those_laid_out(tmp_path, plane):
    """``we.block.steps.laid_out`` steps by ``nb`` at a block's dispatch.
    ``we.block.steps.run`` steps, a ``-device_pairs`` block, at its
    harvest by the third lane of the copy that brings the loss and, a
    block round's, at its dispatch by the host's own count of the
    batches that hold a pair, ``ceil(pair_count / batch)``: over a
    ``train()`` both read what numpy counts from the blocks."""
    from multiverso_tpu.models.wordembedding.distributed import (
        DistributedWordEmbedding)
    corpus = tmp_path / "corpus.txt"
    _corpus(str(corpus))
    batch = 64
    opt = Option(train_file=str(corpus), output_file=str(tmp_path / "v.txt"),
                 embedding_size=16, window_size=2, min_count=1, epoch=1,
                 data_block_size=2400, pair_batch_size=batch,
                 use_adagrad=True, seed=11, **PLANES[plane])
    we = DistributedWordEmbedding(opt)
    we.prepare()
    blocks, inner = [], we._train_block

    def keeping(block, step):
        blocks.append(block)
        return inner(block, step)
    we._train_block = keeping
    names = ("we.block.steps.run", "we.block.steps.laid_out")
    before = [metrics.counter(n).value for n in names]   # outlive a world
    try:
        we.train()
        run, laid_out = (metrics.counter(n).value - b
                         for n, b in zip(names, before))
    finally:
        we.close()
    assert len(blocks) >= 3
    want_run = want_laid_out = 0
    for block in blocks:
        if plane == "device_pairs":
            sent = block.token_sent
            t_pad = next_bucket(len(sent), min_bucket=1024)
            want_laid_out += next_bucket(t_pad // batch, min_bucket=4)
            pair = sent[1:] == sent[:-1]
            live = np.zeros(t_pad, bool)
            live[:len(sent)] = (np.append(pair, False)
                                | np.append(False, pair))
        else:
            want_laid_out += next_bucket(-(-block.pair_count // batch),
                                         min_bucket=4)
            live = block.stacked["output_mask"].any(axis=2)
        want_run += int(live.reshape(-1, batch).any(axis=1).sum())
    assert (run, laid_out) == (want_run, want_laid_out)
    assert 0 < run < laid_out


# -- the compiled program -------------------------------------------------------

def test_the_compiled_loop_copies_no_table(monkeypatch):
    """The four-shard program as the compiler leaves it: ONE loop over
    the tables, whose trip count is no constant (it is read from the
    mask), whose carry holds the four shards once and whose body makes
    no array of a shard's shape besides the in-place row writes' own
    results: no ``copy`` of a shard, inside the loop or around it, and
    still no table gathered
    (beside ``tests/test_device_pairs_sharded.py``'s
    ``test_the_compiled_program_gathers_no_table``)."""
    with _World("four_shards", monkeypatch) as w:
        srv = w.trainer._servers()[0]
        arg = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dtype, sharding=srv._replicated)
        states = tuple(jax.ShapeDtypeStruct(
            s.state["data"].shape, jnp.float32, sharding=s._sharding)
            for s in w.trainer._servers())
        key = jax.random.PRNGKey(0)
        hlo = w.trainer._program(T_PAD, w.nb).lower(
            states, (arg(w.trainer._slots.shape, jnp.int32),),
            arg((T_PAD,), jnp.int32), arg((T_PAD,), jnp.int32),
            arg(key.shape, key.dtype), arg((), jnp.float32)
        ).compile().as_text()
        shard, stored = srv.shard_rows, srv.padded_rows
    # the other loops are the random bits' (threefry), of constant length
    whiles = [ln for ln in re.findall(r"^.* while\(.*$", hlo, flags=re.M)
              if f"f32[{shard},128]" in ln]
    assert len(whiles) == 1
    assert "known_trip_count" not in whiles[0]
    carried = re.findall(rf"f32\[{shard},128\]", whiles[0].split(" while(")[0])
    assert len(carried) == 4
    assert not re.search(rf"= f32\[{shard},128\]\S* copy\(", hlo)
    assert not re.search(rf"\[({stored}|{VOCAB}),128\]", hlo)
    for op in ("all-gather", "all-to-all", "collective-permute"):
        assert op not in hlo, op
