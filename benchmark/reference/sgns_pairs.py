"""Plain skip-gram pair generation with numpy: what word2vec does to a
token stream, for the cell whose pairs the program draws on the device.

For each centre a window ``b ~ U[1, window]``; every word within ``b`` of
it in the same sentence is a context; each (centre, context) pair draws
``negative`` words from the unigram distribution raised to 0.75 and skips
a draw that hits the centre. As in the reference application the context
word is the input and the centre and its negatives are the outputs.

Lanes are laid out as the program lays them (one lane per token and
offset, offsets outermost, unused lanes masked) so that a batch of lanes
holds about as many live pairs as the program's: AdaGrad sums a batch's
gradient by row, so the batch size is part of the mathematics. The random
draws are numpy's and not the program's, so losses agree in distribution
and not lane for lane.
"""

from __future__ import annotations

import numpy as np


def unigram_cdf(counts, power: float = 0.75) -> np.ndarray:
    p = np.asarray(counts, np.float64) ** power
    return np.cumsum(p / p.sum())


def lane_batches(ids: np.ndarray, sent: np.ndarray, window: int,
                 negative: int, cdf: np.ndarray, batch: int,
                 rng: np.random.Generator) -> tuple:
    """(batches, live pairs): batches of ``batch`` lanes for one block of tokens (``ids`` with
    their sentence numbers ``sent``), in ``reference.sgns_adagrad``'s
    form."""
    n = len(ids)
    b = rng.integers(1, window + 1, n)
    centers, contexts, live = [], [], []
    for d in [*range(-window, 0), *range(1, window + 1)]:
        j = np.arange(n) + d
        inside = (j >= 0) & (j < n)
        jj = np.clip(j, 0, n - 1)
        ok = inside & (abs(d) <= b) & (sent[jj] == sent)
        centers.append(np.where(ok, ids, 0))
        contexts.append(np.where(ok, ids[jj], 0))
        live.append(ok)
    centers = np.concatenate(centers).astype(np.int32)
    contexts = np.concatenate(contexts).astype(np.int32)
    live = np.concatenate(live)
    lanes = len(centers)
    negs = np.zeros((lanes, negative), np.int32)
    negs[live] = np.searchsorted(
        cdf, rng.random((int(live.sum()), negative))).astype(np.int32)
    outputs = np.concatenate([centers[:, None], negs], axis=1)
    omask = np.concatenate(
        [live[:, None], live[:, None] & (negs != centers[:, None])],
        axis=1).astype(np.float32)
    labels = np.zeros((lanes, 1 + negative), np.float32)
    labels[:, 0] = 1.0
    out = []
    for at in range(0, lanes, batch):
        sl = slice(at, at + batch)
        pad = batch - len(centers[sl])

        def padded(a):
            return np.concatenate(
                [a, np.zeros((pad,) + a.shape[1:], a.dtype)]) if pad else a

        out.append({"inputs": padded(contexts[sl][:, None]),
                    "input_mask": padded(
                        live[sl][:, None].astype(np.float32)),
                    "outputs": padded(outputs[sl]),
                    "labels": padded(labels[sl]),
                    "output_mask": padded(omask[sl])})
    return out, int(live.sum())
