"""WordEmbedding tests: tier-1 (dictionary/huffman/sampler math) and
tier-3 E2E training on a tiny structured corpus (the reference's
app-as-test pattern, SURVEY.md §4.2)."""

import numpy as np
import pytest

from multiverso_tpu.models.wordembedding.dictionary import Dictionary
from multiverso_tpu.models.wordembedding.huffman import HuffmanEncoder
from multiverso_tpu.models.wordembedding.option import Option
from multiverso_tpu.models.wordembedding.sampler import Sampler


class TestDictionary:
    def test_build_and_prune(self, tmp_path):
        corpus = tmp_path / "c.txt"
        corpus.write_text("a a a b b c\n a b d\n")
        d = Dictionary()
        d.build_from_corpus(str(corpus))
        d.RemoveWordsLessThan(2)
        assert d.Size() == 2  # a (4), b (3)
        assert d.GetWordIdx("a") == 0  # most frequent first
        assert d.GetWordIdx("c") == -1
        assert d.WordCount() == 7

    def test_vocab_roundtrip(self, tmp_path):
        d = Dictionary()
        for w, c in [("x", 10), ("y", 5)]:
            d.Insert(w, c)
        path = str(tmp_path / "vocab.txt")
        d.save_vocab(path)
        d2 = Dictionary.load_vocab(path)
        assert d2.Size() == 2 and d2.GetWordInfo(0).freq == 10

    def test_stopwords(self):
        d = Dictionary(stopwords={"the"})
        d.Insert("the", 100)
        d.Insert("cat", 5)
        assert d.Size() == 1


class TestHuffman:
    def test_codes_prefix_free_and_frequency_ordered(self):
        counts = [100, 50, 20, 10, 5]
        enc = HuffmanEncoder()
        enc.BuildFromTermFrequency(counts)
        codes = []
        for i in range(len(counts)):
            info = enc.GetLabelInfo(i)
            assert len(info.codes) == len(info.points)
            assert all(0 <= p < len(counts) - 1 for p in info.points)
            codes.append("".join(map(str, info.codes)))
        # prefix-free
        for i, a in enumerate(codes):
            for j, b in enumerate(codes):
                if i != j:
                    assert not b.startswith(a)
        # most frequent word gets the shortest code
        assert len(codes[0]) == min(len(c) for c in codes)
        assert enc.max_code_length == max(len(c) for c in codes)

    def test_expected_code_length_optimal(self):
        # Huffman minimizes expected length; against a known small case
        counts = [5, 5, 5, 5]
        enc = HuffmanEncoder()
        enc.BuildFromTermFrequency(counts)
        assert all(len(enc.GetLabelInfo(i).codes) == 2 for i in range(4))


class TestSampler:
    def test_negative_distribution_follows_power_law(self):
        counts = [1000, 100, 10, 1]
        s = Sampler(counts, seed=0)
        draws = s.SampleNegatives(20000)
        freq = np.bincount(draws, minlength=4) / 20000
        assert freq[0] > freq[1] > freq[2]
        expect = np.array(counts, float) ** 0.75
        expect /= expect.sum()
        np.testing.assert_allclose(freq, expect, atol=0.02)

    def test_subsample_keeps_rare_drops_frequent(self):
        counts = [10 ** 6, 10]
        s = Sampler(counts, seed=0)
        ids = np.array([0] * 1000 + [1] * 1000)
        keep = s.KeepMask(ids, sample=1e-3)
        assert keep[1000:].mean() > 0.99     # rare word kept
        assert keep[:1000].mean() < 0.5      # frequent word mostly dropped

    def test_no_subsample_when_disabled(self):
        s = Sampler([5, 5], seed=0)
        assert s.KeepMask(np.array([0, 1]), 0.0).all()


def _make_corpus(path, n_sentences=300, seed=0):
    """Structured corpus: each sentence draws all words from ONE topic of 5
    words (4 topics, 20-word vocab) so same-topic words co-occur heavily."""
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for _ in range(n_sentences):
            topic = rng.integers(4)
            words = [f"w{topic * 5 + rng.integers(5)}" for _ in range(12)]
            f.write(" ".join(words) + "\n")


def _topic_separation(output_file):
    """-> (same_topic_cos, cross_topic_cos) for _make_corpus vectors."""
    lines = open(output_file).read().splitlines()[1:]
    vecs = {l.split()[0]: np.array(l.split()[1:], float) for l in lines}

    def cos(a, b):
        return a @ b / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-9)

    same = np.mean([cos(vecs[f"w{5*t}"], vecs[f"w{5*t + k}"])
                    for t in range(4) for k in range(1, 5)])
    cross = np.mean([cos(vecs[f"w{5*t}"], vecs[f"w{(5*t + 7) % 20}"])
                     for t in range(4)])
    return same, cross


def _run(tmp_path, **kw):
    from multiverso_tpu.models.wordembedding.distributed import (
        DistributedWordEmbedding)
    corpus = tmp_path / "corpus.txt"
    _make_corpus(str(corpus))
    opt = Option(train_file=str(corpus),
                 output_file=str(tmp_path / "vec.txt"),
                 embedding_size=16, window_size=2, negative_num=3,
                 min_count=1, epoch=2, data_block_size=4000,
                 pair_batch_size=256, init_learning_rate=0.05)
    for k, v in kw.items():
        setattr(opt, k, v)
    we = DistributedWordEmbedding(opt)
    avg_loss = we.run()
    we.close()
    return opt, avg_loss


class TestBlockQueue:
    def test_loader_error_surfaces_after_the_queued_blocks(self):
        from multiverso_tpu.models.wordembedding.data import (BlockQueue,
                                                              DataBlock)
        q = BlockQueue(capacity=2)
        q.push(DataBlock(word_count=7))
        q.close(OSError("disk gone"))
        assert q.pop().word_count == 7
        with pytest.raises(OSError, match="disk gone"):
            q.pop()

    def test_clean_close_ends_the_stream(self):
        from multiverso_tpu.models.wordembedding.data import BlockQueue
        q = BlockQueue()
        q.close()
        assert q.pop() is None


def _sentences(path, d):
    from multiverso_tpu.models.wordembedding.data import sentences_from_file
    return [ids.tolist() for ids, _ in sentences_from_file(str(path), d)]


def _counter(name):
    from multiverso_tpu.telemetry import metrics
    return metrics.counter(name).value


class TestBuiltOncePerTrainer:
    """What depends on prepare()'s products alone (the native tokenizer of
    the dictionary, the block program of use_adagrad) is built once per
    trainer: not once a pass, not once a train() call."""

    PLANES = {"host": dict(is_pipeline=False),
              "host_pipeline": dict(is_pipeline=True),
              "device_plane": dict(device_plane=True, is_pipeline=False),
              "device_pairs": dict(device_pairs=True, is_pipeline=False)}

    def _trainer(self, tmp_path, **kw):
        from multiverso_tpu.models.wordembedding.distributed import (
            DistributedWordEmbedding)
        corpus = tmp_path / "corpus.txt"
        _make_corpus(str(corpus), n_sentences=120)
        opt = Option(train_file=str(corpus),
                     output_file=str(tmp_path / "vec.txt"),
                     embedding_size=8, window_size=2, negative_num=3,
                     min_count=1, epoch=3, data_block_size=4000,
                     pair_batch_size=256, use_adagrad=True)
        for k, v in kw.items():
            setattr(opt, k, v)
        return DistributedWordEmbedding(opt)

    @pytest.mark.parametrize("plane", list(PLANES))
    def test_train_builds_no_tokenizer_and_one_block_program(
            self, tmp_path, plane):
        from multiverso_tpu import native
        from multiverso_tpu.telemetry import metrics
        if native.lib() is None:
            pytest.skip("native toolchain unavailable")
        we = self._trainer(tmp_path, **self.PLANES[plane])
        toks, progs = (_counter("we.tokenizer.builds"),
                       _counter("we.block_program.builds"))
        try:
            we.prepare()
            assert _counter("we.tokenizer.builds") == toks + 1
            built = metrics.snapshot()["we.prepare.tokenizer_s"]["value"]
            assert 0.0 < built <= metrics.snapshot()[
                "we.prepare.dictionary_s"]["value"]
            tok, step = we.dictionary._tokenizer, we._step
            assert tok is not None
            first = we.train()          # three passes over the corpus
            programs = 0 if plane == "device_pairs" else 1
            assert _counter("we.block_program.builds") == progs + programs
            program = we._block_scan_cache
            we.opt.epoch = 1
            second = we.train()
            assert np.isfinite(first) and np.isfinite(second)
            # four passes and two train() calls later: the one table, the
            # one step, the one block program
            assert _counter("we.tokenizer.builds") == toks + 1
            assert _counter("we.block_program.builds") == progs + programs
            assert we.dictionary.tokenizer() is tok
            assert we._step is step and we._block_scan_cache is program
        finally:
            we.close()

    @pytest.mark.parametrize("mutate, rebuilt", [
        (lambda d, p: d.Insert("w99", 3), True),
        (lambda d, p: d.Insert("w3", 100), False),    # a count, no new id
        (lambda d, p: d.RemoveWordsLessThan(25), True),    # some of the 20
        (lambda d, p: d.build_from_corpus(p), True),
    ], ids=["Insert_new_word", "Insert_known_word", "RemoveWordsLessThan",
            "build_from_corpus"])
    def test_mutator_cannot_leave_a_stale_tokenizer(self, tmp_path, mutate,
                                                    rebuilt):
        from multiverso_tpu import native
        if native.lib() is None:
            pytest.skip("native toolchain unavailable")
        corpus = tmp_path / "corpus.txt"
        _make_corpus(str(corpus), n_sentences=40)
        extra = tmp_path / "extra.txt"
        extra.write_text("w99 w98 w99 w0\n")
        d = Dictionary()
        d.build_from_corpus(str(corpus))
        d.RemoveWordsLessThan(1)
        tok = d.tokenizer()
        assert tok is not None and d.tokenizer() is tok
        before = _sentences(corpus, d)
        mutate(d, str(extra))
        assert d.Size() > 0
        assert (d._tokenizer is None) == rebuilt
        # the next reader tokenizes by the dictionary as it now stands
        want = [[i for i in (d.GetWordIdx(t) for t in line.split())
                 if i >= 0]
                for line in (corpus.read_text() + extra.read_text())
                .splitlines()]
        both = tmp_path / "both.txt"
        both.write_text(corpus.read_text() + extra.read_text())
        assert _sentences(both, d) == [s for s in want if s]
        assert (d.tokenizer() is tok) == (not rebuilt)
        if not rebuilt:
            assert _sentences(corpus, d) == before

    def test_load_vocab_starts_without_a_tokenizer(self, tmp_path):
        d = Dictionary()
        for w, c in [("x", 10), ("y", 5)]:
            d.Insert(w, c)
        d.tokenizer()
        path = str(tmp_path / "vocab.txt")
        d.save_vocab(path)
        assert Dictionary.load_vocab(path)._tokenizer is None

    @pytest.mark.parametrize("plane", ["host", "device_pairs"])
    def test_without_the_native_library_training_is_the_same(
            self, tmp_path, monkeypatch, plane):
        """No library: prepare() builds nothing, every pass looks words
        up in python, and the job trains the very same stream."""
        from multiverso_tpu import native
        if native.lib() is None:
            pytest.skip("native toolchain unavailable")
        corpus = tmp_path / "corpus.txt"
        _make_corpus(str(corpus), n_sentences=120)
        with_lib = Dictionary()
        with_lib.build_from_corpus(str(corpus))
        want = _sentences(corpus, with_lib)
        losses = {}
        for library in (True, False):
            if not library:
                monkeypatch.setattr(native, "lib", lambda: None)
            builds = _counter("we.tokenizer.builds")
            we = self._trainer(tmp_path, epoch=2, **self.PLANES[plane])
            try:
                we.prepare()
                losses[library] = we.train()
                assert (_counter("we.tokenizer.builds")
                        == builds + (1 if library else 0))
                assert (we.dictionary.tokenizer() is None) == (not library)
                assert _sentences(corpus, we.dictionary) == want
            finally:
                we.close()
        assert losses[True] == losses[False]


class TestEndToEnd:
    def test_skipgram_neg_trains_and_saves(self, tmp_path):
        opt, avg_loss = _run(tmp_path)
        # random sigmoid loss per pair is ~(1+K)*0.69; training must beat it
        assert avg_loss < 0.69 * (1 + opt.negative_num) * 0.9
        header = open(opt.output_file).readline().split()
        assert int(header[0]) == 20 and int(header[1]) == 16
        # same-topic words must be closer than cross-topic words
        lines = open(opt.output_file).read().splitlines()[1:]
        vecs = {l.split()[0]: np.array(l.split()[1:], float) for l in lines}

        def cos(a, b):
            return a @ b / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-9)

        same = np.mean([cos(vecs[f"w{5*t}"], vecs[f"w{5*t + k}"])
                        for t in range(4) for k in range(1, 5)])
        cross = np.mean([cos(vecs[f"w{5*t}"], vecs[f"w{(5*t + 7) % 20}"])
                         for t in range(4)])
        assert same > cross

    def test_cbow(self, tmp_path):
        _, avg_loss = _run(tmp_path, cbow=True)
        assert avg_loss < 0.69 * 4 * 0.9

    def test_hierarchical_softmax(self, tmp_path):
        opt, avg_loss = _run(tmp_path, hs=True, negative_num=0)
        # a pair's loss is summed over its centre's path: log 2 a node
        # while the output rows are zero, so the ceiling to beat is the
        # mean path length over the corpus's words (the tables against the
        # plain reference: tests/test_we_cbow_hs.py)
        d = Dictionary()
        d.build_from_corpus(opt.train_file)
        d.RemoveWordsLessThan(opt.min_count)
        enc = HuffmanEncoder()
        enc.BuildFromTermFrequency(d.counts())
        counts = np.asarray(d.counts())
        ceiling = np.log(2) * (counts * enc.lengths).sum() / counts.sum()
        assert 0 < avg_loss < 0.95 * ceiling

    def test_adagrad(self, tmp_path):
        _, avg_loss = _run(tmp_path, use_adagrad=True,
                           init_learning_rate=0.1)
        assert avg_loss < 0.69 * 4 * 0.9

    def test_no_pipeline(self, tmp_path):
        _, avg_loss = _run(tmp_path, is_pipeline=False)
        assert avg_loss < 0.69 * 4 * 0.9

    @pytest.mark.parametrize("threads", [1, 2])
    def test_loader_failure_fails_main(self, tmp_path, monkeypatch,
                                       threads):
        """A loader thread that dies mid-corpus must fail the run: the
        trainer used to see a short stream, save embeddings trained on
        the first block alone and exit 0."""
        import multiverso_tpu as mv
        from multiverso_tpu.models.wordembedding import distributed
        from multiverso_tpu.models.wordembedding.data import PairGenerator
        corpus = tmp_path / "corpus.txt"
        _make_corpus(str(corpus))
        real = PairGenerator.make_block
        calls = []

        def flaky(self, *args, **kw):
            calls.append(1)
            if len(calls) == 2:
                raise OSError("corpus went away")
            return real(self, *args, **kw)

        monkeypatch.setattr(PairGenerator, "make_block", flaky)
        # main() turns the compile cache on; placed from outside it sets no
        # path, so this process's later compiles stay off the disk
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "jax_cache"))
        out = tmp_path / "vec.txt"
        with pytest.raises(OSError, match="corpus went away"):
            distributed.main(["-train_file", str(corpus), "-output",
                              str(out), "-size", "16", "-min_count", "1",
                              "-data_block_size", "4000", "-threads",
                              str(threads)])
        assert not out.exists()
        # the failed run shut its world down: the next one can start
        mv.MV_Init([])
        mv.MV_ShutDown()

    def test_device_pairs_trains_with_topic_structure(self, tmp_path):
        """-device_pairs 1: the fused on-device generate+train program must
        learn the same topic structure the host pair path learns (same
        marginal pair distribution — windows, subsampling, unigram^0.75
        negatives — different RNG stream)."""
        opt, avg_loss = _run(tmp_path, device_pairs=True)
        assert avg_loss < 0.69 * (1 + opt.negative_num) * 0.9
        lines = open(opt.output_file).read().splitlines()[1:]
        vecs = {l.split()[0]: np.array(l.split()[1:], float) for l in lines}

        def cos(a, b):
            return a @ b / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-9)

        same = np.mean([cos(vecs[f"w{5*t}"], vecs[f"w{5*t + k}"])
                        for t in range(4) for k in range(1, 5)])
        cross = np.mean([cos(vecs[f"w{5*t}"], vecs[f"w{(5*t + 7) % 20}"])
                         for t in range(4)])
        assert same > cross
        assert all(np.all(np.isfinite(v)) for v in vecs.values())

    def test_device_pairs_adagrad(self, tmp_path):
        _, avg_loss = _run(tmp_path, device_pairs=True, use_adagrad=True,
                           init_learning_rate=0.1)
        assert avg_loss < 0.69 * 4 * 0.9

    def test_device_pairs_sparse_adagrad_matches_dense(self, tmp_path,
                                                       monkeypatch):
        """The large-vocab sparse touched-rows adagrad step must produce
        the same tables as the dense full-table step (identical math,
        different data movement) — same seed, same block, two thresholds."""
        import jax.numpy as jnp
        import multiverso_tpu as mv
        from multiverso_tpu.models.wordembedding import device_pairs as dp
        from multiverso_tpu.models.wordembedding.distributed import (
            DistributedWordEmbedding)
        corpus = tmp_path / "corpus.txt"
        _make_corpus(str(corpus))
        results = {}
        for name, threshold in (("dense", 1 << 60), ("sparse", 0)):
            monkeypatch.setattr(dp, "_SPARSE_BYTES", threshold)
            opt = Option(train_file=str(corpus),
                         output_file=str(tmp_path / f"v_{name}.txt"),
                         embedding_size=16, window_size=2, negative_num=3,
                         min_count=1, epoch=1, use_adagrad=True,
                         device_pairs=True, init_learning_rate=0.1)
            we = DistributedWordEmbedding(opt)
            we.run()
            results[name] = we.comm.pull_embeddings()
            we.close()
        np.testing.assert_allclose(results["sparse"], results["dense"],
                                   rtol=2e-5, atol=2e-6)

    def test_device_pairs_cbow(self, tmp_path):
        """-device_pairs covers CBOW: context lanes mean-combine through
        the step's imask (round-3 rejected this mode; round 4 fuses it).
        Must learn the corpus topic structure, not just reduce loss."""
        opt, loss = _run(tmp_path, device_pairs=True, cbow=True,
                         use_adagrad=True, init_learning_rate=0.1)
        assert loss < 0.69 * 4 * 0.9
        same, cross = _topic_separation(opt.output_file)
        assert same > cross

    def test_device_pairs_hs(self, tmp_path):
        """-device_pairs covers hierarchical softmax: the center's Huffman
        path gathers from the uploaded (points, 1-codes) tables. A
        misaligned gather could still shrink the loss, so the corpus
        topic structure is the real assertion."""
        opt, loss = _run(tmp_path, device_pairs=True, hs=True,
                         negative_num=0, use_adagrad=True,
                         init_learning_rate=0.1, epoch=3)
        assert 0 < loss < 0.69 * 6
        same, cross = _topic_separation(opt.output_file)
        assert same > cross

    def test_device_pairs_cbow_hs(self, tmp_path):
        opt, loss = _run(tmp_path, device_pairs=True, cbow=True, hs=True,
                         negative_num=0, use_adagrad=True,
                         init_learning_rate=0.1, epoch=3)
        assert 0 < loss < 0.69 * 6
        same, cross = _topic_separation(opt.output_file)
        assert same > cross

    def test_device_plane_matches_host_plane(self, tmp_path):
        """-device_plane 1: fetch/train/push entirely in HBM must produce
        the same embeddings as the host-plane run (same verb order, same
        math — only the transport differs)."""
        (tmp_path / "host").mkdir()
        (tmp_path / "dev").mkdir()
        # pipeline off: the host pipeline prefetches the NEXT block before
        # the current push lands (deliberate staleness, reference
        # ps_model-style) — the device plane always fetches fresh, so the
        # apples-to-apples comparison is unpipelined
        opt_h, _ = _run(tmp_path / "host", use_adagrad=True,
                        init_learning_rate=0.1, is_pipeline=False)
        opt_d, _ = _run(tmp_path / "dev", use_adagrad=True,
                        init_learning_rate=0.1, device_plane=True,
                        is_pipeline=False)
        host = open(opt_h.output_file).read().splitlines()[1:]
        dev = open(opt_d.output_file).read().splitlines()[1:]
        hv = {l.split()[0]: np.array(l.split()[1:], np.float64)
              for l in host}
        dv = {l.split()[0]: np.array(l.split()[1:], np.float64) for l in dev}
        assert hv.keys() == dv.keys()
        for w in hv:
            np.testing.assert_allclose(dv[w], hv[w], rtol=1e-3, atol=1e-4)

    def test_device_plane_fetch_waits_for_the_tables_last_apply(
            self, tmp_path, monkeypatch):
        """One block's row sets in HBM at a time: before a table's rows are
        fetched (and allocated), the host waits until that table's last
        apply has run. A CPU run cannot read device memory, so the test
        holds the order of the calls."""
        import jax
        from multiverso_tpu.tables.matrix_table import MatrixServerTable
        events = []
        wait, fetch = jax.block_until_ready, MatrixServerTable.device_fetch_rows

        def waiting(x):
            if isinstance(x, dict) and "data" in x:
                events.append(("wait", id(x["data"])))
            return wait(x)

        def fetching(self, ids):
            events.append(("fetch", id(self.state["data"])))
            return fetch(self, ids)
        monkeypatch.setattr(jax, "block_until_ready", waiting)
        monkeypatch.setattr(MatrixServerTable, "device_fetch_rows", fetching)
        _run(tmp_path, use_adagrad=True, device_plane=True,
             is_pipeline=False, epoch=1)
        fetches = [i for i, e in enumerate(events) if e[0] == "fetch"]
        assert len(fetches) >= 8            # two blocks or more, four tables
        for i in fetches:
            assert events[i - 1] == ("wait", events[i][1])

    def test_device_plane_cbow_and_hs(self, tmp_path):
        """The device-plane path must serve every model variant (CBOW,
        hierarchical softmax), not just skipgram+NEG."""
        (tmp_path / "a").mkdir()
        (tmp_path / "b").mkdir()
        _, loss_cbow = _run(tmp_path / "a", cbow=True, device_plane=True,
                            is_pipeline=False)
        assert loss_cbow < 0.69 * 4 * 0.9
        _, loss_hs = _run(tmp_path / "b", hs=True, negative_num=0,
                          device_plane=True, is_pipeline=False)
        assert loss_hs > 0

    def test_binary_output(self, tmp_path):
        opt, _ = _run(tmp_path, output_binary=True)
        raw = open(opt.output_file, "rb").read()
        assert raw.split(b"\n", 1)[0] == b"20 16"

    def test_option_parse_args(self):
        opt = Option.parse_args(["-size", "64", "-train_file", "x.txt",
                                 "-cbow", "1", "-negative", "10",
                                 "-use_adagrad", "1", "-epoch", "3"])
        assert opt.embedding_size == 64 and opt.cbow and \
            opt.negative_num == 10 and opt.use_adagrad and opt.epoch == 3


def _wide_vocab(path, words=64):
    """A vocabulary file of ``words`` words whose first 20 are
    _make_corpus's: the others are in the tables and in no sentence."""
    with open(path, "w") as f:
        for i in range(words):
            f.write(f"w{i} {1000 - i}\n")


class TestBlockScanStep:
    """The block round's scanned AdaGrad step is chosen from the state it
    is given: model.make_train_step over every fetched row, or, once the
    state passes device_pairs._SPARSE_BYTES and is whole on one device,
    -device_pairs' touched-rows step over the rows a batch names, its pad
    lanes in the trash row of the communicator's rung-long training copy."""

    TABLES = ("ie", "eo", "ie_g2", "eo_g2")

    def _world(self, monkeypatch, threshold, one_device=True):
        import jax
        import multiverso_tpu as mv
        from multiverso_tpu.models.wordembedding import device_pairs
        monkeypatch.setattr(device_pairs, "_SPARSE_BYTES", threshold)
        mv.MV_Init([], devices=jax.devices()[:1] if one_device else None)
        return mv

    def _trainer(self, tmp_path, **kw):
        from multiverso_tpu.models.wordembedding.distributed import (
            DistributedWordEmbedding)
        corpus, vocab = tmp_path / "corpus.txt", tmp_path / "vocab.txt"
        _make_corpus(str(corpus), n_sentences=120)
        _wide_vocab(str(vocab))
        opt = Option(train_file=str(corpus), read_vocab_file=str(vocab),
                     output_file=str(tmp_path / "vec.txt"),
                     embedding_size=16, window_size=2, negative_num=3,
                     min_count=1, epoch=1, data_block_size=4000,
                     pair_batch_size=256, use_adagrad=True,
                     init_learning_rate=0.1, is_pipeline=False)
        for k, v in kw.items():
            setattr(opt, k, v)
        we = DistributedWordEmbedding(opt)
        we.prepare()
        return we

    def _tables(self, we):
        comm = we.comm
        every = np.arange(comm.vocab_size, dtype=np.int32)
        tables = (comm.input_table, comm.output_table, comm.ie_g2_table,
                  comm.eo_g2_table)
        return {name: np.array(t.GetRows(every))
                for name, t in zip(self.TABLES, tables)}

    @pytest.mark.parametrize("mode", ["skipgram_neg", "cbow"])
    @pytest.mark.parametrize("device_plane", [0, 1])
    def test_touched_rows_step_matches_dense(self, tmp_path, monkeypatch,
                                             device_plane, mode):
        """Same seed and corpus, two thresholds: the pulled embeddings and
        both accumulator tables agree (the bound of
        test_device_pairs_sparse_adagrad_matches_dense)."""
        got = {}
        for kind, threshold in (("dense", 1 << 60), ("touched", 0)):
            mv = self._world(monkeypatch, threshold)
            try:
                (tmp_path / kind).mkdir()
                we = self._trainer(tmp_path / kind, epoch=2,
                                   device_plane=bool(device_plane),
                                   cbow=mode == "cbow")
                blocks = _counter("we.blocks")
                touched = _counter("we.block_scan.touched_rows_blocks")
                we.train()
                blocks = _counter("we.blocks") - blocks
                assert blocks >= 4
                assert (_counter("we.block_scan.touched_rows_blocks")
                        - touched) == (blocks if kind == "touched" else 0)
                got[kind] = dict(self._tables(we),
                                 pulled=we.comm.pull_embeddings())
            finally:
                mv.MV_ShutDown()
        assert np.array_equal(got["dense"]["pulled"], got["dense"]["ie"])
        for name in ("pulled", "ie_g2", "eo_g2", "eo"):
            assert got["dense"][name].any()
            np.testing.assert_allclose(got["touched"][name],
                                       got["dense"][name],
                                       rtol=2e-5, atol=2e-6, err_msg=name)

    def test_spare_rows_of_the_training_copy(self, tmp_path, monkeypatch):
        """One block at threshold 0. The state is a rung long and its last
        row takes the step's pad lanes, so the last FETCHED row, a live
        word, trains as in the dense run; a row no lane names keeps its
        first value and a zero accumulator; what is pushed back is as long
        as the ids."""
        from multiverso_tpu.models.wordembedding.communicator import (
            training_rows)
        from multiverso_tpu.models.wordembedding.model import init_embedding
        from multiverso_tpu.tables.matrix_table import MatrixServerTable
        got, named, pushed, states = {}, {}, [], []
        apply = MatrixServerTable.device_apply_rows

        def applying(self, ids, delta, *a, **kw):
            pushed.append((len(ids), delta.shape[0]))
            return apply(self, ids, delta, *a, **kw)
        monkeypatch.setattr(MatrixServerTable, "device_apply_rows", applying)
        for kind, threshold in (("dense", 1 << 60), ("touched", 0)):
            mv = self._world(monkeypatch, threshold)
            try:
                (tmp_path / kind).mkdir()
                we = self._trainer(tmp_path / kind, device_plane=True,
                                   data_block_size=1 << 20)
                inner, fetch = we._train_block, \
                    we.comm.request_parameter_device

                def keeping(block, step):
                    st = block.stacked
                    named["ie"] = block.input_rows[np.unique(st["inputs"])]
                    named["eo"] = block.output_rows[np.unique(st["outputs"])]
                    named["last"] = (block.input_rows[-1],
                                     block.output_rows[-1])
                    return inner(block, step)

                def fetching(input_rows, output_rows):
                    state, rows = fetch(input_rows, output_rows)
                    states.append(({k: getattr(state, k).shape[0]
                                    for k in self.TABLES},
                                   {k: v.shape[0] for k, v in rows.items()}))
                    return state, rows
                we._train_block = keeping
                we.comm.request_parameter_device = fetching
                blocks = _counter("we.blocks")
                we.train()
                assert _counter("we.blocks") == blocks + 1
                got[kind] = self._tables(we)
            finally:
                mv.MV_ShutDown()
        # a rung with a row to spare, whichever step reads it; the push is
        # as long as the ids
        for trained, fetched in states:
            assert trained == {k: training_rows(n)
                               for k, n in fetched.items()}
            assert all(trained[k] > fetched[k] for k in fetched)
        assert len(pushed) == 8 and all(n == rows for n, rows in pushed)
        vocab = got["touched"]["ie"].shape[0]
        first = {"ie": init_embedding(vocab, 16, 1),
                 "eo": np.zeros((vocab, 16), np.float32)}
        for side in ("ie", "eo"):
            rest = np.setdiff1d(np.arange(vocab), named[side])
            if side == "ie":
                assert len(rest) >= vocab - 20      # no sentence holds them
            for kind in got:
                assert np.array_equal(got[kind][side][rest],
                                      first[side][rest])
                assert not got[kind][side + "_g2"][rest].any()
        # the last fetched rows are named (the input set is the sorted set
        # of the words named; the output set, over half the vocabulary, is
        # every row): without a trash row the pad lanes would land there
        last_in, last_out = named["last"]
        assert last_in in named["ie"] and last_in < vocab - 1
        for side, row in (("ie", last_in), ("eo", last_out)):
            for name in (side, side + "_g2"):
                np.testing.assert_allclose(got["touched"][name][row],
                                           got["dense"][name][row],
                                           rtol=2e-5, atol=2e-6)
        assert got["dense"]["ie_g2"][last_in].any()
        for name in self.TABLES:
            np.testing.assert_allclose(got["touched"][name],
                                       got["dense"][name],
                                       rtol=2e-5, atol=2e-6, err_msg=name)

    @pytest.mark.parametrize("mode", ["skipgram_neg", "cbow"])
    @pytest.mark.parametrize("device_plane", [0, 1])
    def test_the_looped_update_is_the_update_of_every_lane(
            self, tmp_path, monkeypatch, device_plane, mode):
        """Same seed and corpus, the scan's touched-rows step as it is
        (an update walks the batch's distinct rows in chunks of 64 pairs:
        four lanes a pair out, four in under CBOW) and with the update of
        every lane in its place: the four tables after two passes equal
        to the bit, and the loss the same float."""
        from multiverso_tpu.models.wordembedding import device_pairs
        from tests.test_we_cbow_hs import full_lane_step
        looped = device_pairs._make_sparse_adagrad_step
        got = {}
        for kind, make in (("looped", looped), ("full_lane", lambda: (
                lambda *args: (*full_lane_step()(*args), 0)))):
            monkeypatch.setattr(device_pairs, "_make_sparse_adagrad_step",
                                make)
            mv = self._world(monkeypatch, 0)
            try:
                (tmp_path / kind).mkdir()
                we = self._trainer(tmp_path / kind, epoch=2,
                                   pair_batch_size=64,
                                   device_plane=bool(device_plane),
                                   cbow=mode == "cbow")
                touched = _counter("we.block_scan.touched_rows_blocks")
                loss = we.train()
                assert _counter("we.block_scan.touched_rows_blocks") > touched
                got[kind] = dict(self._tables(we), loss=loss)
            finally:
                mv.MV_ShutDown()
        assert got["looped"]["loss"] == got["full_lane"]["loss"]
        for name in self.TABLES:
            assert got["looped"][name].any()
            assert np.array_equal(got["looped"][name],
                                  got["full_lane"][name]), name

    @pytest.mark.parametrize("case, threshold, kw, one_device, touched", [
        ("default_constant_tiny_vocabulary", None, {}, True, False),
        ("threshold_0", 0, {}, True, True),
        ("threshold_0_host_plane", 0, {"device_plane": False}, False, True),
        ("threshold_0_plain_sgd", 0, {"use_adagrad": False}, True, False),
        ("threshold_0_state_over_8_devices", 0, {}, False, False),
    ])
    def test_the_choice(self, tmp_path, monkeypatch, case, threshold, kw,
                        one_device, touched):
        from multiverso_tpu.models.wordembedding import device_pairs
        if threshold is None:
            threshold = device_pairs._SPARSE_BYTES
            assert threshold == 64 << 20
        mv = self._world(monkeypatch, threshold, one_device)
        try:
            we = self._trainer(tmp_path, **dict({"device_plane": True}, **kw))
            before = (_counter("we.blocks"),
                      _counter("we.block_scan.touched_rows_blocks"))
            assert np.isfinite(we.train())
            blocks = _counter("we.blocks") - before[0]
            assert blocks >= 2
            assert (_counter("we.block_scan.touched_rows_blocks")
                    - before[1]) == (blocks if touched else 0)
            assert list(we._block_scan_cache) == [
                (we.opt.use_adagrad, touched)]
        finally:
            mv.MV_ShutDown()

    def test_row_counts_inside_a_rung_share_one_scan_program(
            self, tmp_path, monkeypatch):
        """The scan program is compiled for the training copy's rung, not
        for the block's row count: the guard on the cell's set-up time (a
        program with four Mosaic calls and two sorts a distinct count)."""
        from multiverso_tpu.models.wordembedding.communicator import (
            training_rows)
        from multiverso_tpu.models.wordembedding.data import PairGenerator
        mv = self._world(monkeypatch, 0)
        try:
            we = self._trainer(tmp_path, device_plane=True)
            generator = PairGenerator(we.opt, we.dictionary, we.sampler,
                                      we.huffman)
            rng = np.random.default_rng(5)
            blocks = [generator.make_block(
                [rng.permutation(words)[:12].astype(np.int32)
                 for _ in range(8)], 96) for words in (17, 23)]
            counts = [len(b.input_rows) for b in blocks]
            assert counts[0] != counts[1] and max(counts) < 32
            assert len({training_rows(n) for n in counts}) == 1
            assert len({b.stacked["inputs"].shape for b in blocks}) == 1
            builds = _counter("we.block_program.builds")
            touched = _counter("we.block_scan.touched_rows_blocks")
            for block in blocks:
                loss, pairs = we._train_block(block, we._step)
                assert np.isfinite(float(loss)) and pairs == block.pair_count
            assert _counter("we.block_program.builds") == builds + 1
            assert (_counter("we.block_scan.touched_rows_blocks")
                    == touched + 2)
            (program,) = we._block_scan_cache.values()
            assert program._cache_size() == 1
        finally:
            mv.MV_ShutDown()

    #: step -> (device_pairs._SPARSE_BYTES, use_adagrad); plain SGD has no
    #: touched-rows step (its update is the scatter-add at any size)
    STEPS = {"dense_adagrad": (1 << 60, True), "touched_adagrad": (0, True),
             "dense_sgd": (0, False)}
    #: pairs a block holds, at 256 a batch -> (batches that hold one, nb)
    PAIRS = {"one_pair": (1, 1, 4),
             "whole_batches": (5 * 256, 5, 8),
             "one_pair_over_a_bucket": (4 * 256 + 1, 5, 8),
             "a_full_bucket": (4 * 256, 4, 4)}

    def _cut_blocks(self, we, pair_counts):
        """Blocks of the same sentences cut to ``pair_counts`` pairs."""
        from multiverso_tpu.models.wordembedding.data import PairGenerator
        generator = PairGenerator(we.opt, we.dictionary, we.sampler,
                                  we.huffman)
        rng = np.random.default_rng(5)
        arrays = generator._skipgram_neg_arrays(
            [rng.integers(0, 20, 12).astype(np.int32) for _ in range(60)])
        assert len(arrays[0]) > max(pair_counts)
        return [generator._finalize_block(*(a[:n] for a in arrays),
                                          word_count=720)
                for n in pair_counts]

    @pytest.mark.parametrize("pairs", list(PAIRS))
    @pytest.mark.parametrize("step", list(STEPS))
    def test_the_live_steps_leave_what_every_laid_out_step_leaves(
            self, tmp_path, monkeypatch, step, pairs):
        """The program handed ``ceil(pair_count / batch)`` leaves the state
        and the loss that the program handed ``nb`` (every laid-out step,
        what ``lax.scan`` ran) leaves, entry for entry; one step fewer does
        not: the last batch that holds a pair, be it one, is trained."""
        import jax.numpy as jnp
        from multiverso_tpu.models.wordembedding.distributed import (
            _steps_to_run)
        threshold, use_adagrad = self.STEPS[step]
        pair_count, n_live, nb = self.PAIRS[pairs]
        mv = self._world(monkeypatch, threshold)
        try:
            we = self._trainer(tmp_path, device_plane=True,
                               use_adagrad=use_adagrad)
            (block,) = self._cut_blocks(we, [pair_count])
            assert block.pair_count == pair_count
            st = block.stacked
            assert st["inputs"].shape[0] == nb
            # the pairs fill the laid-out lanes in order: what the count
            # the host hands over rests on
            live = st["output_mask"].reshape(nb, -1).any(axis=1)
            assert live.tolist() == [True] * n_live + [False] * (nb - n_live)
            tensors = [jnp.asarray(st[k]) for k in (
                "inputs", "input_mask", "outputs", "labels", "output_mask")]

            def run(steps):
                state, _ = we.comm.request_parameter_device(
                    block.input_rows, block.output_rows)
                assert _steps_to_run(state, block.pair_count,
                                     we.opt.pair_batch_size, nb) == n_live
                program, touched = we._block_scan_fn(state, we._step)
                assert touched == (step == "touched_adagrad")
                state, loss = program(state, *tensors, jnp.float32(0.1),
                                      np.int32(steps))
                return ([np.asarray(rows) for rows in state
                         if rows is not None], np.asarray(loss))

            (live_state, live_loss), (full_state, full_loss), \
                (short_state, short_loss) = run(n_live), run(nb), \
                run(n_live - 1)
            assert len(live_state) == (4 if use_adagrad else 2)
            for got, want in zip(live_state, full_state):
                assert np.array_equal(got, want)
            assert live_loss.tobytes() == full_loss.tobytes()
            assert live_loss > 0
            assert short_loss < live_loss
            assert not all(np.array_equal(got, short)
                           for got, short in zip(live_state, short_state))
            (program,) = we._block_scan_cache.values()
            assert program._cache_size() == 1
        finally:
            mv.MV_ShutDown()

    def test_a_state_over_more_than_one_process_runs_every_laid_out_step(
            self):
        """The trip count is the host's own block's: a program that is one
        collective program over processes gets ``nb`` on each of them."""
        import collections
        from multiverso_tpu.models.wordembedding.distributed import (
            _steps_to_run)
        Rows = collections.namedtuple("Rows", "is_fully_addressable")
        here, spread = Rows(True), Rows(False)
        assert _steps_to_run((here, here, None, None), 257, 256, 4) == 2
        assert _steps_to_run((here, here, here, here), 256, 256, 4) == 1
        assert _steps_to_run((here, spread, here, spread), 257, 256, 4) == 4

    def test_pair_counts_inside_a_bucket_share_one_scan_program(
            self, tmp_path, monkeypatch):
        """The trip count is an operand and never a shape: blocks of 300
        and 700 pairs (2 and 3 batches of the 4 laid out) dispatch ONE
        compiled program, and the two counters read the steps run of
        those laid out."""
        mv = self._world(monkeypatch, 0)
        try:
            we = self._trainer(tmp_path, device_plane=True)
            blocks = self._cut_blocks(we, [300, 700])
            assert len({b.stacked["inputs"].shape for b in blocks}) == 1
            names = ("we.block_program.builds", "we.block.steps.run",
                     "we.block.steps.laid_out")
            before = [_counter(n) for n in names]
            for block in blocks:
                loss, pairs = we._train_block(block, we._step)
                assert float(loss) > 0 and pairs == block.pair_count
            assert [_counter(n) - b for n, b in zip(names, before)] == [
                1, 2 + 3, 4 + 4]
            (program,) = we._block_scan_cache.values()
            assert program._cache_size() == 1
        finally:
            mv.MV_ShutDown()


class TestDevicePairsStats:
    def test_stats_lanes_exact_and_flush_proof(self):
        """The block stats ride ONE int32 array: loss as bitcast f32 bits
        (lane 0), pair count as a plain int32 (lane 1). The count must be
        exact past 2^24 and must NOT live in a float lane — a bitcast
        int-in-f32 is a denormal that TPUs flush to zero in flight (the
        bug this test pins: every block's pair count read back 0)."""
        import jax.numpy as jnp
        from jax import lax
        from multiverso_tpu.models.wordembedding.device_pairs import _LazyStats
        for loss, count in ((123.456, 7), (0.0, 0), (1e-20, 2**24 + 3),
                            (3.25e6, 75_000_000)):
            loss_bits = lax.bitcast_convert_type(
                jnp.float32(loss), jnp.int32)
            stats = jnp.stack([loss_bits, jnp.int32(count)])
            assert stats.dtype == jnp.int32   # int lanes are never flushed
            got_loss = float(_LazyStats(stats, 0, bits=True))
            got_count = int(_LazyStats(stats, 1))
            assert got_count == count
            np.testing.assert_allclose(got_loss, np.float32(loss))

    def test_production_stats_array_is_integer_typed(self, mv_env):
        """Exercise the REAL program: the trainer's returned stats must be
        backed by an int32 array (a float-typed one would flush the count
        lane to zero on TPU) and round-trip a correct count."""
        from multiverso_tpu.models.wordembedding.communicator import (
            Communicator)
        from multiverso_tpu.models.wordembedding.device_pairs import (
            DevicePairsTrainer, _LazyStats)
        import jax.numpy as jnp
        opt = Option(embedding_size=8, window_size=2, negative_num=2,
                     device_pairs=True, pair_batch_size=64)
        comm = Communicator(opt, vocab_size=50)
        tr = DevicePairsTrainer(opt, comm, counts=[10] * 50)
        ids = np.arange(40, dtype=np.int32) % 50
        sent = (np.arange(40, dtype=np.int32) // 8).astype(np.int32)
        loss, pairs = tr.train_block(ids, sent, 0.01)
        assert isinstance(loss, _LazyStats) and isinstance(pairs,
                                                           _LazyStats)
        assert loss._arr.dtype == jnp.int32, loss._arr.dtype
        assert loss._arr is pairs._arr       # one shared fetch
        n = int(pairs)
        # 5 sentences x 8 tokens, W<=2 windows: a plausible range
        assert 20 <= n <= 40 * 4, n
        assert np.isfinite(float(loss)) and float(loss) > 0
