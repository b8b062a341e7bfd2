"""The fused generate-and-train program over tables of more than one
shard (``-device_pairs 1``, the touched-rows AdaGrad step per shard under
``shard_map``): against the same program on one shard, against the plain
reference on batches drawn on the host, on blocks that name one shard or
the shards' edges alone, and in the compiled module's text.

``_SPARSE_BYTES`` is lowered so that the touched-rows step is the one
that runs at a test's size; the tables are 128 lanes wide as on the chip.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import multiverso_tpu as mv
from multiverso_tpu.models.wordembedding import device_pairs as dp
from multiverso_tpu.models.wordembedding.communicator import Communicator
from multiverso_tpu.models.wordembedding.model import TrainState
from multiverso_tpu.models.wordembedding.option import Option
from multiverso_tpu.parallel.mesh import SERVER_AXIS

VOCAB, DIM, SEED, LR = 400, 128, 11, 0.025   # four shards of 100 rows
NAMES = ("input", "output", "input_g2", "output_g2")


@pytest.fixture(autouse=True)
def _sparse_step(monkeypatch):
    monkeypatch.setattr(dp, "_SPARSE_BYTES", 0)
    dp._PROGRAM_CACHE.clear()
    yield
    dp._PROGRAM_CACHE.clear()


class _World:
    """A world of ``shards`` devices holding the app's four tables."""

    def __init__(self, shards, counts=None, batch=256):
        mv.MV_Init(["-num_workers=1"], devices=jax.devices()[:shards])
        self.opt = Option(embedding_size=DIM, window_size=2, negative_num=3,
                          use_adagrad=True, device_pairs=True,
                          pair_batch_size=batch, seed=SEED,
                          init_learning_rate=LR)
        self.comm = Communicator(self.opt, VOCAB)
        counts = np.arange(VOCAB, 0, -1) if counts is None else counts
        self.trainer = dp.DevicePairsTrainer(self.opt, self.comm, counts)
        self.servers = self.trainer._servers()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        mv.MV_ShutDown()

    def tables(self):
        """The four tables' logical rows, as a read sees them."""
        return {name: np.array(srv._from_storage(np.asarray(
            srv.state["data"]))) for name, srv in zip(NAMES, self.servers)}

    def poison_trash_rows(self):
        """NaN in every shard's trash row of every table: a trash row
        that is read back shows wherever it lands."""
        for srv in self.servers:
            raw = np.array(srv.state["data"]).reshape(
                srv.num_servers, srv.shard_rows, srv.store_cols)
            raw[:, srv.block_rows] = np.nan
            srv.state = {**srv.state, "data": srv._zoo.mesh_ctx.place(
                raw.reshape(srv.padded_rows, srv.store_cols),
                srv._sharding)}


def _tokens(rng, words, sentences=40, length=12):
    """A block's token stream over ``words``: (ids, sentence numbers)."""
    ids = rng.choice(words, sentences * length).astype(np.int32)
    return ids, np.repeat(np.arange(sentences, dtype=np.int32), length)


def _train(world, blocks):
    loss = pairs = 0.0
    for ids, sent in blocks:
        got = world.trainer.train_block(ids, sent, LR)
        loss, pairs = loss + float(got[0]), pairs + int(got[1])
    return loss, int(pairs)


# -- (a) four shards against one ----------------------------------------------

def test_four_shards_train_what_one_shard_trains():
    """The pair drawing stays ONE stream (every shard holds the block's
    tokens and the same key), so the pair count is equal; a fetched row is
    a sum of one row and three zeros, exact, and a shard updates its rows
    by the arithmetic one shard uses. What may differ is the order of
    float32 sums inside a batch's contractions and segment sums, which the
    compiler chooses a program: 1e-5 relative on the loss, 2e-6 on an
    entry (entries reach 0.2; here the CPU's compiler gives the two
    programs the same order and every entry agrees to the last bit)."""
    rng = np.random.default_rng(5)
    blocks = [_tokens(rng, VOCAB) for _ in range(2)]
    got = {}
    for shards in (1, 4):
        with _World(shards) as w:
            assert (w.servers[0].num_servers == shards
                    and "data" in w.servers[0].state)
            loss, pairs = _train(w, blocks)
            got[shards] = (loss, pairs, w.tables())
    assert got[4][1] == got[1][1] > 1000
    assert got[4][0] == pytest.approx(got[1][0], rel=1e-5)
    for name in NAMES:
        np.testing.assert_allclose(got[4][2][name], got[1][2][name],
                                   rtol=0, atol=2e-6, err_msg=name)


# -- (b) the sharded step against the plain reference --------------------------

def _sharded_step(world):
    srv = world.servers[0]
    step = dp._make_sparse_adagrad_step(lanes=srv.device_local_lanes)

    def run(states, inputs, imask, outputs, labels, omask, lr):
        state, loss, _ = step(TrainState(*states), inputs, imask, outputs,
                              labels, omask, lr)
        return tuple(state), loss
    rows, whole = P(SERVER_AXIS, None), P()
    return jax.jit(jax.shard_map(
        run, mesh=srv._mesh, in_specs=((rows,) * 4,) + (whole,) * 6,
        out_specs=((rows,) * 4, whole), check_vma=False),
        donate_argnums=(0,))


def test_the_sharded_step_against_the_plain_reference():
    """Batches drawn on the host by the benchmark's numpy pair generator,
    the very batches through the plain float32 reference: the loss and the
    rows within ``we_rows``' limits (1e-5 relative; 99 % of entries within
    1e-4, the worst within one step, 0.025)."""
    from benchmark.reference import sgns_adagrad, sgns_pairs
    rng = np.random.default_rng(7)
    counts = np.arange(VOCAB, 0, -1)
    cdf = sgns_pairs.unigram_cdf(counts)
    batches = []
    for _ in range(2):
        ids, sent = _tokens(rng, np.arange(0, VOCAB, 2))  # odd words idle
        batches += sgns_pairs.lane_batches(ids, sent, 2, 3, cdf, 256, rng)[0]
    with _World(4) as w:
        program = _sharded_step(w)
        states, total = w.trainer._take_states(), 0.0
        for b in batches:
            states, loss = program(
                states, *(jnp.asarray(b[k]) for k in (
                    "inputs", "input_mask", "outputs", "labels",
                    "output_mask")), jnp.float32(LR))
            total += float(loss)
        w.trainer._put_states(states)
        got = w.tables()
    ref_total, in_ids, in_rows, out_ids, out_rows = sgns_adagrad.train_epoch(
        batches, VOCAB, DIM, SEED, LR)
    assert total == pytest.approx(ref_total, rel=1e-5)
    for name, ids, rows in (("input", in_ids, in_rows),
                            ("output", out_ids, out_rows)):
        gaps = np.abs(got[name][ids] - rows)
        assert np.quantile(gaps, 0.99) <= 1e-4 and gaps.max() <= 0.025, name
    # rows no lane names: initial values and zeros, bit for bit
    idle = np.setdiff1d(np.arange(VOCAB), in_ids)
    assert np.array_equal(got["input"][idle],
                          sgns_adagrad.init_input(VOCAB, DIM, SEED)[idle])
    assert not got["input_g2"][idle].any()
    assert not got["output"][np.setdiff1d(np.arange(VOCAB), out_ids)].any()


# -- (c) one shard's words, and the shards' edges ------------------------------

EDGES = np.array([0, 99, 100, 199, 200, 299, 300, 399])


@pytest.mark.parametrize("words", [np.arange(200, 300), EDGES],
                         ids=["one_shard", "edges_of_every_shard"])
def test_rows_never_named_stay_and_trash_rows_stay_out(words):
    """A block whose tokens all live on shard 2, and one whose words are
    every shard's first and last row: negatives are drawn from the block's
    words alone (no other word has a slot), so every other row of all four
    tables is never named and keeps its bits; the trash rows, poisoned
    with NaN and written by every foreign and pad lane, never come back."""
    counts = np.zeros(VOCAB, np.int64)
    counts[words] = 100
    rng = np.random.default_rng(9)
    with _World(4, counts=counts) as w:
        before = w.tables()
        w.poison_trash_rows()
        loss, pairs = _train(w, [_tokens(rng, words)])
        after = w.tables()
    assert np.isfinite(loss) and pairs > 500
    idle = np.setdiff1d(np.arange(VOCAB), words)
    for name in NAMES:
        assert np.isfinite(after[name]).all(), name
        assert np.array_equal(after[name][idle], before[name][idle]), name
    assert (after["input"][words] != before["input"][words]).any(axis=1).all()
    assert (after["input_g2"][words] > 0).any(axis=1).all()
    assert after["output"][words].any(axis=1).all()


def test_the_tokens_are_counted_by_the_shard_that_owns_them():
    from multiverso_tpu.telemetry import metrics
    rng = np.random.default_rng(3)
    ids, sent = _tokens(rng, np.arange(200, 300))
    ids[:7] = 399
    with _World(4) as w:
        name = "we.block.tokens.shard{}".format
        at = [metrics.counter(name(k)).value for k in range(4)]
        _train(w, [(ids, sent)])
        moved = [metrics.counter(name(k)).value - at[k] for k in range(4)]
    assert moved == [0, 0, len(ids) - 7, 7]


# -- (d) the compiled module ----------------------------------------------------

def test_the_compiled_program_gathers_no_table():
    """The four-shard program as the compiler leaves it: its collectives
    are all-reduces of fetched rows (no all-gather, nothing that moves a
    table) and no instruction holds an array of a table's whole rows: a
    device sees its shard, 101 rows of the 404 stored."""
    rng = np.random.default_rng(1)
    ids, sent = _tokens(rng, VOCAB)
    with _World(4) as w:
        srv = w.servers[0]
        t_pad = 1024
        program = w.trainer._program(t_pad, 16)
        whole = srv._replicated
        arg = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
            shape, dtype, sharding=whole)
        states = tuple(jax.ShapeDtypeStruct(
            s.state["data"].shape, jnp.float32, sharding=s._sharding)
            for s in w.servers)
        key = jax.random.PRNGKey(0)
        hlo = program.lower(
            states, (arg(w.trainer._slots.shape, jnp.int32),),
            arg((t_pad,), jnp.int32), arg((t_pad,), jnp.int32),
            arg(key.shape, key.dtype), arg((), jnp.float32)
        ).compile().as_text()
        stored, shard = srv.padded_rows, srv.shard_rows
    assert "all-reduce" in hlo
    for op in ("all-gather", "all-to-all", "collective-permute",
               "reduce-scatter"):
        assert op not in hlo, op
    assert re.search(rf"f32\[{shard},128\]", hlo)
    assert not re.search(rf"\[({stored}|{VOCAB}),128\]", hlo)
