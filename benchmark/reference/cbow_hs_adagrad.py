"""Plain CBOW with hierarchical softmax under AdaGrad: the reference the
cell ``we_cbow_hs`` is held to (word2vec's ``-cbow 1 -hs 1``; the
reference application's Applications/WordEmbedding/src/wordembedding.cpp:
58-73 ``FeedForward``'s CBOW branch, :75-119 ``BPOutputLayer``'s
hierarchical-softmax branch, src/huffman_encoder.*).

For a centre word ``w`` at position ``t`` of a sentence, with the shrunk
window ``b ~ U{1..window}``:

    C   = {w[t+d] : 0 < |d| <= b, inside the sentence}     the context
    h   = (1/|C|) * sum_{c in C} ie[c]                      its average

and with ``w``'s Huffman path ``(n_1..n_L)`` of inner nodes (rows of the
output table, ``0 <= n < V-1``) and codes ``(c_1..c_L)``:

    f_j   = sigmoid(h . eo[n_j]),   y_j = 1 - c_j,   err_j = y_j - f_j
    loss  = -sum_j [y_j log f_j + (1 - y_j) log(1 - f_j)]
    grad eo[n_j] += err_j * h
    e     = sum_j err_j * eo[n_j]            (the rows before the update)
    grad ie[c]   += e    for every c in C    (whole, not divided by |C|)

Over a lane-batch of ``batch`` centres the gradients of equal rows are
summed first; then for each touched row ``g2 += grad**2`` and
``row += lr * grad / sqrt(g2 + 1e-12)`` where ``g2 > 1e-10`` (the
application's AdaGrad). A centre with an empty ``C`` is no example.
Departures, which the program documents too: a batch's squared gradient,
summed by row, lands before that batch's update (the application goes
example by example), and the logarithms take ``f + 1e-7``.

The tree is built here from the counts by the textbook heap (ties by
count, then by node number: a word before an inner node, an older inner
node before a newer one) and a word's path is walked node by node.
Windows are drawn from numpy's generator; nothing else of a pass is
random. Straightforward float32 ``jax.numpy`` at
``default_matmul_precision("highest")``: no tables, no scan, no kernels,
one dispatch a batch, nothing of the program imported. It works in a
compact row space: only rows that some lane of the pass names exist,
which is all that a pass from fresh tables can change.

Kept twice, byte for byte (``tests/test_we_cbow_hs.py`` holds the two
equal): ``benchmark/reference/cbow_hs_adagrad.py`` decides the cell's
``correct`` and ``multiverso_tpu/models/wordembedding/cbow_hs_reference.py``
is the copy the tier-1 tests compare the program with, as
``updaters/reference.py`` and ``tables/share_reference.py`` are kept.
"""

from __future__ import annotations

import heapq

import numpy as np

EPS = 1e-10


def init_input(vocab: int, dim: int, seed: int) -> np.ndarray:
    """word2vec's input initialisation, uniform(-0.5, 0.5) / dim, from
    numpy's default generator at ``seed``. Output rows and both
    accumulators start at zero."""
    rng = np.random.default_rng(seed)
    return ((rng.random((vocab, dim), np.float32) - 0.5) / dim).astype(
        np.float32)


# -- the tree --------------------------------------------------------------

def huffman_tree(counts) -> tuple:
    """(parent, code) of the ``2V - 1`` nodes, words first, inner node
    ``k`` numbered ``V + k`` in the order it is made; the last is the
    root. The two nodes of least (count, number) merge; the lesser turns
    0, the other 1."""
    n = len(counts)
    heap = [(int(c), i) for i, c in enumerate(counts)]
    heapq.heapify(heap)
    parent, code = [0] * (2 * n - 1), [0] * (2 * n - 1)
    for node in range(n, 2 * n - 1):
        c0, i0 = heapq.heappop(heap)
        c1, i1 = heapq.heappop(heap)
        parent[i0] = parent[i1] = node
        code[i1] = 1
        heapq.heappush(heap, (c0 + c1, node))
    return parent, code


def paths(tree, words) -> tuple:
    """(points, codes, lengths) of ``words``, root first: ``points[i,
    :lengths[i]]`` are the output rows (inner node less ``V``) on word
    ``words[i]``'s path and ``codes[i, :lengths[i]]`` its turns; zero past
    a word's length."""
    parent, code = tree
    n = (len(parent) + 1) // 2
    root = 2 * n - 2
    walked = []
    for w in np.asarray(words).tolist():
        pts, cds, node = [], [], w
        while node != root:
            cds.append(code[node])
            node = parent[node]
            pts.append(node - n)
        walked.append((pts[::-1], cds[::-1]))
    longest = max((len(p) for p, _ in walked), default=0)
    points = np.zeros((len(walked), longest), np.int32)
    codes = np.zeros((len(walked), longest), np.int32)
    lengths = np.zeros(len(walked), np.int32)
    for i, (pts, cds) in enumerate(walked):
        lengths[i] = len(pts)
        points[i, :len(pts)] = pts
        codes[i, :len(cds)] = cds
    return points, codes, lengths


# -- the examples ----------------------------------------------------------

def contexts(ids: np.ndarray, sent: np.ndarray, window: int,
             rng: np.random.Generator) -> tuple:
    """One lane a token of a block (``ids`` with their sentence numbers
    ``sent``): (context words (n, 2 * window), which of them are live).
    A token none of whose lanes is live is no example."""
    n = len(ids)
    b = rng.integers(1, window + 1, n)
    words, live = [], []
    for d in [*range(-window, 0), *range(1, window + 1)]:
        j = np.arange(n) + d
        jj = np.clip(j, 0, n - 1)
        ok = (j >= 0) & (j < n) & (abs(d) <= b) & (sent[jj] == sent)
        words.append(np.where(ok, ids[jj], 0))
        live.append(ok)
    return (np.stack(words, axis=1).astype(np.int32),
            np.stack(live, axis=1))


# -- the step --------------------------------------------------------------

def context_mean(in_rows, imask):
    import jax.numpy as jnp
    count = jnp.maximum(imask.sum(axis=1, keepdims=True), 1.0)
    return (in_rows * imask[:, :, None]).sum(axis=1) / count


def sum_by_row(like, ids, grads):
    """The gradient table: lanes that name one row are summed."""
    import jax.numpy as jnp
    return jnp.zeros_like(like).at[ids].add(grads)


def make_step(hidden=context_mean, by_row=sum_by_row, store=None):
    """The batch step, jitted. ``hidden`` and ``by_row`` are the two places
    where a wrong program differs in kind and not in rounding, and
    ``store`` rounds what is kept between steps through another dtype:
    ``benchmark/tests/test_we_cbow_hs.py`` swaps them to show what the
    cell's limits refuse. The defaults are the mathematics above."""
    import jax
    import jax.numpy as jnp

    def step(state, inputs, imask, outputs, labels, omask, lr):
        ie, eo, ie_g2, eo_g2 = state
        dim = ie.shape[1]
        h = hidden(ie[inputs], imask)                          # (P, D)
        out_rows = eo[outputs]                                 # (P, L, D)
        f = jax.nn.sigmoid(jnp.einsum("pd,pld->pl", h, out_rows))
        err = (labels - f) * omask
        loss = -jnp.sum(omask * (labels * jnp.log(f + 1e-7)
                                 + (1 - labels) * jnp.log(1 - f + 1e-7)))
        e = jnp.einsum("pl,pld->pd", err, out_rows)
        eo_grad = by_row(eo, outputs.reshape(-1),
                         (err[:, :, None] * h[:, None, :]).reshape(-1, dim))
        ie_grad = by_row(ie, inputs.reshape(-1),
                         (e[:, None, :] * imask[:, :, None]).reshape(-1, dim))
        eo_g2 = eo_g2 + eo_grad * eo_grad
        ie_g2 = ie_g2 + ie_grad * ie_grad
        eo = eo + jnp.where(eo_g2 > EPS,
                            lr * eo_grad / jnp.sqrt(eo_g2 + 1e-12), 0.0)
        ie = ie + jnp.where(ie_g2 > EPS,
                            lr * ie_grad / jnp.sqrt(ie_g2 + 1e-12), 0.0)
        state = (ie, eo, ie_g2, eo_g2)
        if store is not None:
            state = tuple(t.astype(store).astype(jnp.float32)
                          for t in state)
        return state, loss

    return jax.jit(step, donate_argnums=(0,))


def train_pass(blocks, counts, dim: int, seed: int, lr: float, window: int,
               batch: int, rng: np.random.Generator, tree=None, step=None,
               store=None, start=None) -> dict:
    """One pass from fresh tables over ``blocks``, a list of (token ids,
    their sentence numbers): every block's tokens in order, ``batch``
    centres a step, the last step of a block short. ``start`` (``in_ids``
    with ``ie``, ``out_ids`` with ``eo``; a row it does not hold is fresh)
    takes the place of the fresh tables: at ``lr`` 0 the pass then reads
    what those tables lose on these examples and changes nothing.

    -> ``loss`` (summed over every live output lane), ``examples``,
    ``in_ids`` (ascending) with their trained ``ie`` rows, ``ie_g2``
    accumulator rows and path ``lengths``, ``out_ids`` (output rows: inner
    nodes less V, ascending) with ``eo`` and ``eo_g2``, and ``tree``."""
    import jax
    import jax.numpy as jnp
    vocab = len(counts)
    tree = tree if tree is not None else huffman_tree(counts)
    step = step if step is not None else make_step(store=store)
    in_ids = np.unique(np.concatenate([ids for ids, _ in blocks]))
    points, codes, lengths = paths(tree, in_ids)
    lane = np.arange(points.shape[1])[None, :]
    on_path = lane < lengths[:, None]
    out_ids = np.unique(points[on_path])
    at_out = np.searchsorted(out_ids, points) * on_path   # pad: row 0
    zeros = lambda n: jnp.zeros((n, dim), jnp.float32)  # noqa: E731
    ie0 = init_input(vocab, dim, seed)[in_ids]
    eo0 = np.zeros((len(out_ids), dim), np.float32)
    if start is not None:
        for fresh, ids, kept, rows in (
                (ie0, in_ids, start["in_ids"], start["ie"]),
                (eo0, out_ids, start["out_ids"], start["eo"])):
            at = np.minimum(np.searchsorted(kept, ids), len(kept) - 1)
            held = kept[at] == ids
            fresh[held] = rows[at[held]]
    if store is not None:
        ie0 = ie0.astype(store).astype(np.float32)
    state = (jnp.asarray(ie0), jnp.asarray(eo0), zeros(len(in_ids)),
             zeros(len(out_ids)))
    losses, examples = [], 0
    with jax.default_matmul_precision("highest"):
        for ids, sent in blocks:
            words, live = contexts(ids, sent, window, rng)
            centre = np.searchsorted(in_ids, ids)
            is_example = live.any(axis=1)
            examples += int(is_example.sum())
            for at in range(0, len(ids), batch):
                sl = slice(at, at + batch)
                if not is_example[sl].any():
                    continue    # no gradient: AdaGrad leaves every row
                c = centre[sl]
                state, loss = step(
                    state,
                    jnp.asarray((np.searchsorted(in_ids, words[sl])
                                 * live[sl]).astype(np.int32)),
                    jnp.asarray(live[sl].astype(np.float32)),
                    jnp.asarray(at_out[c].astype(np.int32)),
                    jnp.asarray((1 - codes[c]).astype(np.float32)),
                    jnp.asarray((on_path[c] & is_example[sl, None]).astype(
                        np.float32)),
                    jnp.float32(lr))
                losses.append(loss)
        total = float(np.sum([np.float64(x) for x in jax.device_get(losses)]))
    ie, eo, ie_g2, eo_g2 = (np.asarray(t) for t in state)
    return {"loss": total, "examples": examples, "tree": tree,
            "in_ids": in_ids, "ie": ie, "ie_g2": ie_g2, "lengths": lengths,
            "out_ids": out_ids, "eo": eo, "eo_g2": eo_g2}
