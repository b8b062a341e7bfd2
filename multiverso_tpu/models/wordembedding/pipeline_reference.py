"""Plain reference of the WordEmbedding app's block pipeline: skip-gram
with negative sampling under AdaGrad, rows moved by Get and Add, the next
block's rows fetched while this one trains (``-is_pipeline 1`` on the host
plane; the reference application's Applications/WordEmbedding
src/distributed_wordembedding.cpp:147-252, the prefetch :203-215, and
src/communicator.cpp:117-206, RequestParameter / AddDeltaParameter).

The tables are four matrices: input rows ``ie``, output rows ``eo`` and
their AdaGrad accumulators ``ie_g2`` / ``eo_g2``. A block ``b`` names a set
of input rows and a set of output rows and holds lane-batches of pairs
over them. With ONE worker and prefetch depth one:

    fetched_b = the four row sets, copied out of the tables with the
                deltas of blocks 0 .. b-2 added and no other
                (blocks 0 and 1: the tables as they were handed in)
    trained_b = fetched_b trained batch by batch (``_step``)
    delta_b   = trained_b - fetched_b, of all four tables
    tables   += delta_b, exactly once; all of them by the end

``prefetch_depth=0`` is the sequential round (block ``b`` sees the deltas
of blocks 0 .. b-1), what the app does with ``-is_pipeline 0``.

A batch's step is the plain skip-gram step of
``benchmark/reference/sgns_adagrad.py`` (wordembedding.cpp: FeedForward,
BPOutputLayer, the AdaGrad branch), over the block's own rows.

Departures from the upstream, which the program documents too: a batch's
squared gradient, summed by row, lands before that batch's update (the
upstream applies pair by pair); the logarithms take ``f + 1e-7``; the
upstream leaves to its threads' timing which of the earlier blocks' deltas
a prefetched Get sees (its prefetch thread races the trainers' Adds), here
the order above is fixed; the upstream trains a block with several OpenMP
threads on one copy of the rows, here one worker trains batch after batch.

Straightforward float32 ``jax.numpy`` at
``default_matmul_precision("highest")``: no tables, no engine, no scan, no
kernels, one dispatch a batch, nothing of the program imported. The row
ids are indices into whatever matrices are handed in: the caller may work
in a compact row space (only the rows some block names), which is all that
training can change.

Kept twice, byte for byte (``tests/test_we_pipeline.py`` holds the two
equal): ``benchmark/reference/sgns_adagrad_pipeline.py`` decides the cell's
``correct`` and ``multiverso_tpu/models/wordembedding/pipeline_reference.py``
is the copy the tier-1 tests compare the program with, as
``cbow_hs_reference.py`` is kept.
"""

from __future__ import annotations

import numpy as np

EPS = 1e-10


def init_input(vocab: int, dim: int, seed: int) -> np.ndarray:
    """word2vec's input initialisation, uniform(-0.5, 0.5) / dim, from
    numpy's default generator at ``seed``. Output rows and both
    accumulators start at zero."""
    rng = np.random.default_rng(seed)
    return ((rng.random((vocab, dim), np.float32) - 0.5) / dim).astype(
        np.float32)


def _step(state, inputs, imask, outputs, labels, omask, lr):
    import jax
    import jax.numpy as jnp
    ie, eo, ie_g2, eo_g2 = state
    dim = ie.shape[1]
    in_rows = ie[inputs]                                   # (P, Cin, D)
    denom = jnp.maximum(imask.sum(axis=1, keepdims=True), 1.0)
    h = (in_rows * imask[:, :, None]).sum(axis=1) / denom  # (P, D)
    out_rows = eo[outputs]                                 # (P, Cout, D)
    f = jax.nn.sigmoid(jnp.einsum("pd,pcd->pc", h, out_rows))
    err = (labels - f) * omask
    loss = -jnp.sum(omask * (labels * jnp.log(f + 1e-7)
                             + (1 - labels) * jnp.log(1 - f + 1e-7)))
    hid_err = jnp.einsum("pc,pcd->pd", err, out_rows)
    eo_grad = jnp.zeros_like(eo).at[outputs.reshape(-1)].add(
        (err[:, :, None] * h[:, None, :]).reshape(-1, dim))
    ie_grad = jnp.zeros_like(ie).at[inputs.reshape(-1)].add(
        (hid_err[:, None, :] * imask[:, :, None]).reshape(-1, dim))
    eo_g2 = eo_g2 + eo_grad * eo_grad
    ie_g2 = ie_g2 + ie_grad * ie_grad
    eo = eo + jnp.where(eo_g2 > EPS, lr * eo_grad / jnp.sqrt(eo_g2 + 1e-12),
                        0.0)
    ie = ie + jnp.where(ie_g2 > EPS, lr * ie_grad / jnp.sqrt(ie_g2 + 1e-12),
                        0.0)
    return (ie, eo, ie_g2, eo_g2), loss


def train_blocks(blocks, tables, lr: float, prefetch_depth: int = 1,
                 dtype="float32"):
    """Train ``blocks`` in order on ``tables``.

    ``blocks``: a list of dicts with ``input_rows`` and ``output_rows``
    (int row ids into the tables, distinct; a row no batch names may
    repeat, its delta is zero, so a caller can lay every block's sets
    out to one length on a spare row) and ``batches``, a list of
    dicts with ``inputs`` (P, Cin) and ``outputs`` (P, Cout) as int32
    positions in the BLOCK's row sets and float32 ``input_mask``,
    ``labels``, ``output_mask``. ``tables``: ``(ie, eo, ie_g2, eo_g2)``,
    matrices of one width, kept in ``dtype`` (float32 is the deployment's;
    a test reads the precision below it through this); a block's copy is
    trained in float32 whatever they are kept in.

    -> (loss summed over every unmasked output lane, the four tables as
    numpy with every block's delta added)."""
    import jax
    import jax.numpy as jnp
    tables = [jnp.asarray(t, dtype) for t in tables]
    step = jax.jit(_step, donate_argnums=(0,))
    waiting = []        # (block, its row ids a table, its four deltas)
    losses = []

    def land(upto: int) -> None:
        """Add the deltas of the blocks up to ``upto`` to the tables."""
        while waiting and waiting[0][0] <= upto:
            _, ids, deltas = waiting.pop(0)
            for k in range(4):
                tables[k] = tables[k].at[ids[k]].add(deltas[k].astype(dtype))

    with jax.default_matmul_precision("highest"):
        for b, block in enumerate(blocks):
            land(b - 1 - prefetch_depth)
            ids = [jnp.asarray(np.asarray(block[rows], np.int32))
                   for rows in ("input_rows", "output_rows") * 2]
            fetched = [tables[k][ids[k]].astype(jnp.float32)
                       for k in range(4)]
            state = tuple(jnp.array(rows) for rows in fetched)  # its own
            for batch in block["batches"]:
                if not batch["output_mask"].any():
                    continue    # padding: no gradient, AdaGrad leaves rows
                state, loss = step(
                    state, jnp.asarray(batch["inputs"]),
                    jnp.asarray(batch["input_mask"]),
                    jnp.asarray(batch["outputs"]),
                    jnp.asarray(batch["labels"]),
                    jnp.asarray(batch["output_mask"]), jnp.float32(lr))
                losses.append(loss)
            waiting.append((b, ids, [trained - rows for trained, rows
                                     in zip(state, fetched)]))
            del state, fetched
        land(len(blocks))
    total = float(np.sum([np.float64(x) for x in jax.device_get(losses)]))
    return total, [np.asarray(t.astype(jnp.float32)) for t in tables]
