"""Traffic generation, the replay reference and the counter readers."""

import numpy as np
import pytest

from benchmark.harness import clock, program, traffic
from benchmark.reference import sgns_adagrad, sgns_pairs, table_replay


@pytest.mark.parametrize("law", ["uniform", "loguniform"])
def test_unique_ids_are_distinct_in_range_and_seeded(law):
    a = traffic.unique_ids(np.random.default_rng(3), 100_000, 5000, law)
    b = traffic.unique_ids(np.random.default_rng(3), 100_000, 5000, law)
    assert np.array_equal(a, b) and a.dtype == np.int32
    assert len(np.unique(a)) == 5000 and a.min() >= 0 and a.max() < 100_000


def test_loguniform_ids_make_the_first_shard_hot():
    ids = traffic.unique_ids(np.random.default_rng(0), 12_000_000, 65536,
                             "loguniform")
    assert (ids < 3_000_000).mean() > 0.7


def test_corpus_is_whole_sentences_over_the_vocabulary(tmp_path):
    vocab, corpus, counts = traffic.write_vocab_and_corpus(
        str(tmp_path), 2000, 4000, 100, 20, 1e6, seed=5)
    with open(vocab) as f:
        lines = f.read().split("\n")[:-1]
    assert len(lines) == 2000 and lines[0].split()[0] == "w0"
    assert int(lines[0].split()[1]) == counts[0] and counts.min() >= 1
    with open(corpus) as f:
        sentences = [ln.split() for ln in f.read().split("\n")[:-1]]
    assert len(sentences) == 200 and all(len(s) == 20 for s in sentences)
    for s in sentences:                       # one topic a sentence
        assert len({int(w[1:]) // 100 for w in s}) == 1


def test_table_replay():
    sample = np.array([2, 5, 9], np.int32)
    adds = [(np.array([5, 7, 2]), np.array([[1.0, 2], [3, 4], [5, 6]]), 2),
            (np.array([9, 5]), 3, 1),
            (np.array([2]), np.array([[9.0, 9]]), 0)]
    want = np.array([[10, 12], [5, 7], [3, 3]], np.float32)
    assert np.array_equal(table_replay.expected_rows(sample, 2, adds), want)


def test_percentile_is_nearest_rank():
    assert clock.percentile(range(1, 101), 95) == 95.0
    assert clock.percentile([3.0], 95) == 3.0
    assert clock.since_process_start() > 0


def test_counter_and_histogram_deltas():
    before = {"c": {"value": 3.0}, "h": {"count": 2, "sum": 0.5}}
    after = {"c": {"value": 10.0}, "h": {"count": 6, "sum": 2.5}}
    assert program.counter_delta(before, after, "c") == 7.0
    assert program.counter_delta(before, after, "absent") is None
    assert program.histogram_delta(before, after, "h") == (4, 2.0)
    assert program.histogram_delta({}, after, "h") == (6, 2.5)
    assert program.histogram_delta(before, after, "absent") is None


def test_reference_epoch_learns_and_touches_only_named_rows():
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 50, 400).astype(np.int32)
    sent = np.repeat(np.arange(20), 20).astype(np.int32)
    cdf = sgns_pairs.unigram_cdf(np.ones(50))
    batches, live = sgns_pairs.lane_batches(ids, sent, 5, 5, cdf, 256, rng)
    assert live > 0 and all(b["inputs"].shape == (256, 1) for b in batches)
    total, in_ids, in_rows, out_ids, out_rows = sgns_adagrad.train_epoch(
        batches * 3, 50, 16, seed=1, lr=0.025)
    assert np.isfinite(total) and total / (3 * live) < 6 * np.log(2.0)
    assert set(in_ids) <= set(range(50)) and in_rows.shape == (len(in_ids),
                                                               16)
    assert np.abs(out_rows).max() > 0
