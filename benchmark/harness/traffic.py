"""The one general generator: from a mix's parameters and the seed to the
inputs the program receives. A mix is a data file under ``traffic/``; a new
mix that these laws can draw needs no code.
"""

from __future__ import annotations

import os

import numpy as np


def _zipf(n: int) -> np.ndarray:
    p = 1.0 / np.arange(1, n + 1)
    return p / p.sum()


def unique_ids(rng: np.random.Generator, rows: int, k: int,
               law: str) -> np.ndarray:
    """``k`` distinct row ids below ``rows``: the first ``k`` distinct
    values of an independent stream drawn by ``law``.

    ``uniform``: every row equally likely. ``loguniform``: the id is a
    rank, ranks log-uniform over [1, rows] (Zipf with exponent 1 over the
    whole table, the law of a frequency-sorted dictionary), so low ids are
    in nearly every set and block-sharded tables see their first shard
    most."""
    got = np.empty(0, np.int64)
    while True:
        n = 2 * (k - len(got)) + 64
        if law == "uniform":
            draw = rng.integers(0, rows, n)
        elif law == "loguniform":
            draw = np.floor(np.exp(rng.random(n) * np.log(rows))).astype(
                np.int64) - 1
        else:
            raise ValueError(f"unknown id law {law!r}")
        both = np.concatenate([got, np.clip(draw, 0, rows - 1)])
        _, first = np.unique(both, return_index=True)
        got = both[np.sort(first)]
        if len(got) >= k:
            return got[:k].astype(np.int32)


def id_pool(rng, rows: int, k: int, law: str, sets: int) -> list:
    return [unique_ids(rng, rows, k, law) for _ in range(sets)]


def whole_number_deltas(rng, shape, low: int = -4, high: int = 4):
    """float32 deltas that are small whole numbers: sums of thousands of
    them are exact in float32 in any order."""
    return rng.integers(low, high + 1, shape).astype(np.float32)


def write_vocab_and_corpus(workdir: str, vocab: int, corpus_words: int,
                           topic_words: int, sentence_words: int,
                           nominal_words: float, seed: int):
    """A vocabulary file (``word count`` lines, word2vec's format) of
    ``vocab`` words and a corpus of ``corpus_words`` words over it.

    After ``chip_smoke.py``'s ``write_corpus``: words come in topics of
    ``topic_words`` that only ever share a sentence with each other;
    topics, and words within a topic, are Zipf-distributed, so there is
    structure to learn and a long tail of rows. The counts in the
    vocabulary file are each word's expected count in a corpus of
    ``nominal_words`` words (never under 1): the file a user brings from a
    counting pass over the full corpus, of which this run trains a stretch.
    -> (vocab path, corpus path, counts by word number)."""
    rng = np.random.default_rng(seed)
    topics = vocab // topic_words
    if topics * topic_words != vocab or corpus_words % sentence_words:
        raise ValueError("vocabulary must be whole topics and the corpus "
                         "whole sentences")
    p_topic, p_within = _zipf(topics), _zipf(topic_words)
    counts = np.maximum(1, np.rint(
        np.outer(p_topic, p_within).ravel() * nominal_words)).astype(
            np.int64)
    vocab_path = os.path.join(workdir, "vocab.txt")
    with open(vocab_path, "w") as f:
        f.write("\n".join(map("w%d %d".__mod__,
                              zip(range(vocab), counts.tolist()))))
        f.write("\n")
    sentences = corpus_words // sentence_words
    topic = rng.choice(topics, sentences, p=p_topic)
    within = rng.choice(topic_words, (sentences, sentence_words), p=p_within)
    ids = topic[:, None] * topic_words + within
    used, inverse = np.unique(ids, return_inverse=True)
    words = np.char.add("w", used.astype(str))[inverse.reshape(ids.shape)]
    corpus_path = os.path.join(workdir, "corpus.txt")
    with open(corpus_path, "w") as f:
        f.write("\n".join(" ".join(row) for row in words))
        f.write("\n")
    return vocab_path, corpus_path, counts
