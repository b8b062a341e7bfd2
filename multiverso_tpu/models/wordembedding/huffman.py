"""Huffman encoder for hierarchical softmax.

Behavioral equivalent of reference
Applications/WordEmbedding/src/huffman_encoder.h/.cpp: build a Huffman tree
over word frequencies; each word gets (codes, points) — the 0/1 turns and
the inner-node ids along its root path. Inner node ids are offset into the
output-embedding table rows [0, vocab_size-1) like word2vec's syn1.

The tree and every word's path are ARRAYS (``points``, ``codes``,
``lengths``): one sort of the counts, one two-queue merge (the only step
that is sequential by nature: two comparisons a merge, no heap, no tuple),
then ``max_code_length`` rounds of ``node = parent[node]`` over all words
at once: a vocabulary of millions builds in seconds. Ties break as a heap
of (count, node number) breaks them: a word before an inner node, an older
inner node before a newer one.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Sequence

import numpy as np


@dataclass
class HuffLabelInfo:
    codes: List[int] = field(default_factory=list)    # 0/1 path turns
    points: List[int] = field(default_factory=list)   # inner-node row ids


def _merge(counts: np.ndarray):
    """The tree over ``counts``: (parent, binary) of the ``2n - 1`` nodes,
    words first, inner node ``k`` numbered ``n + k`` in the order it is
    made; the root is the last. The two smallest nodes by (count, number)
    merge, the smaller turning 0 and the other 1. Inner nodes are made in
    ascending order of count and number, so they are a queue of their own
    and the sorted words another: the smallest node is at one of the two
    heads, and on equal counts it is the word."""
    n = len(counts)
    order = np.argsort(counts, kind="stable")
    leaf_count = counts[order].tolist()
    leaf_count.append(float("inf"))         # the words' queue never ends
    leaf = order.tolist()
    parent = [0] * (2 * n - 1)
    binary = bytearray(2 * n - 1)
    made: List[int] = []                    # inner node k's count
    i = j = 0                               # the two heads
    for k in range(n - 1):
        node = n + k
        if j < k and made[j] < leaf_count[i]:
            first = made[j]
            parent[n + j] = node
            j += 1
        else:
            first = leaf_count[i]
            parent[leaf[i]] = node
            i += 1
        if j < k and made[j] < leaf_count[i]:
            second, other = made[j], n + j
            j += 1
        else:
            second, other = leaf_count[i], leaf[i]
            i += 1
        parent[other] = node
        binary[other] = 1
        made.append(first + second)
    return (np.asarray(parent, np.int64),
            np.frombuffer(binary, np.uint8))


class HuffmanEncoder:
    """``points[w, :lengths[w]]`` are the inner-node rows on word ``w``'s
    path from the root down and ``codes[w, :lengths[w]]`` its turns; what
    lies past a word's length is zero."""

    def __init__(self):
        self.points = np.zeros((0, 0), np.int32)
        self.codes = np.zeros((0, 0), np.uint8)
        self.lengths = np.zeros(0, np.int32)
        self.max_code_length = 0

    def BuildFromTermFrequency(self, counts: Sequence[int]) -> None:
        counts = np.asarray(counts, np.int64)
        n = len(counts)
        if n == 0:
            return
        parent, binary = _merge(counts)
        root = 2 * n - 2
        parent[root] = root             # a walk that is done stays there
        # every word's distance from the root: the rounds until all the
        # leaf-to-root walks, advanced together, have arrived
        node = np.arange(n)
        lengths = np.zeros(n, np.int32)
        while True:
            alive = node != root
            if not alive.any():
                break
            lengths += alive
            node = parent[node]
        self.lengths = lengths
        self.max_code_length = mc = int(lengths.max())
        # the same walks again, a column a round, leaf first ...
        up_points = np.empty((n, mc), np.int32)
        up_codes = np.empty((n, mc), np.uint8)
        node = np.arange(n)
        for r in range(mc):
            up_codes[:, r] = binary[node]
            node = parent[node]
            up_points[:, r] = node - n
        # ... and turned root first, the words of one length at a time
        self.points = np.zeros((n, mc), np.int32)
        self.codes = np.zeros((n, mc), np.uint8)
        for length in np.unique(lengths).tolist():
            if length:
                words = np.nonzero(lengths == length)[0]
                self.points[words, :length] = up_points[words, length - 1::-1]
                self.codes[words, :length] = up_codes[words, length - 1::-1]

    def GetLabelInfo(self, word_idx: int) -> HuffLabelInfo:
        n = int(self.lengths[word_idx])
        return HuffLabelInfo(self.codes[word_idx, :n].tolist(),
                             self.points[word_idx, :n].tolist())

    def VocabSize(self) -> int:
        return len(self.lengths)
