"""Plain skip-gram with negative sampling under AdaGrad: the reference the
WordEmbedding cells are held to.

Straightforward float32 ``jax.numpy``: no tables, no scan, no kernels, one
dispatch a batch, ``default_matmul_precision("highest")``. It follows the
reference application (Applications/WordEmbedding/src/wordembedding.cpp:
FeedForward, BPOutputLayer, the AdaGrad branch) with the one departure the
program documents too: a batch's squared gradient, summed by row, lands
before that batch's update (the reference applies pair by pair).

It works in a compact row space: only rows that some lane of the epoch
names exist, which is all that an epoch from fresh tables can change.
"""

from __future__ import annotations

import numpy as np

EPS = 1e-10


def init_input(vocab: int, dim: int, seed: int) -> np.ndarray:
    """word2vec's input initialisation, uniform(-0.5, 0.5) / dim, from
    numpy's default generator at ``seed``: the seeded weights the system
    and the reference both start from. Output rows and both accumulators
    start at zero."""
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


def train_epoch(batches, vocab: int, dim: int, seed: int, lr: float):
    """One epoch from fresh tables over ``batches``: a list of dicts with
    ``inputs`` (P, Cin) and ``outputs`` (P, Cout) as int32 vocabulary ids
    and float32 ``input_mask``, ``labels``, ``output_mask``.

    -> (loss summed over every unmasked output lane, input row ids, their
    trained rows, output row ids, their trained rows), rows as numpy."""
    import jax
    import jax.numpy as jnp
    in_ids = np.unique(np.concatenate([b["inputs"].ravel()
                                       for b in batches]))
    out_ids = np.unique(np.concatenate([b["outputs"].ravel()
                                        for b in batches]))
    ie0 = init_input(vocab, dim, seed)[in_ids]
    zeros = lambda n: jnp.zeros((n, dim), jnp.float32)  # noqa: E731
    state = (jnp.asarray(ie0), zeros(len(out_ids)), zeros(len(in_ids)),
             zeros(len(out_ids)))
    step = jax.jit(_step, donate_argnums=(0,))
    with jax.default_matmul_precision("highest"):
        losses = []
        for b in batches:
            if not b["output_mask"].any():
                continue        # padding: no gradient, AdaGrad leaves rows
            state, loss = step(
                state,
                jnp.asarray(np.searchsorted(in_ids, b["inputs"]).astype(
                    np.int32)),
                jnp.asarray(b["input_mask"]),
                jnp.asarray(np.searchsorted(out_ids, b["outputs"]).astype(
                    np.int32)),
                jnp.asarray(b["labels"]), jnp.asarray(b["output_mask"]),
                jnp.float32(lr))
            losses.append(loss)
        total = float(np.sum([np.float64(x) for x in jax.device_get(losses)]))
    return (total, in_ids, np.asarray(state[0]), out_ids,
            np.asarray(state[1]))
