"""Native runtime tests: build the C++ library, run its self-test binary,
and exercise the C API + fast readers from python over ctypes
(the reference's c_api.cpp / binding path, SURVEY.md §2a/§2g)."""

import ctypes
import os
import shutil
import subprocess

import numpy as np
import pytest

NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")

pytestmark = pytest.mark.skipif(shutil.which("g++") is None,
                                reason="no C++ toolchain")


@pytest.fixture(scope="module")
def native_build():
    result = subprocess.run(["make", "-C", NATIVE_DIR, "-j4"],
                            capture_output=True, text=True, timeout=300)
    assert result.returncode == 0, result.stderr
    return NATIVE_DIR


class TestSelftestBinary:
    def test_cpp_selftest(self, native_build):
        """Runs the full C++ suite: utils, async tables, BSP sync protocol,
        updaters, readers."""
        result = subprocess.run([os.path.join(native_build, "mvt_selftest")],
                                capture_output=True, text=True, timeout=120)
        assert result.returncode == 0, result.stdout + result.stderr
        assert "ALL NATIVE TESTS OK" in result.stdout


@pytest.mark.slow
class TestSanitizers:
    """Round-16: slow-marked (each sanitizer target is a full -O1
    instrumented rebuild of the runtime when stale, plus a minutes-long
    instrumented run) — `pytest -m slow tests/test_native.py` is the CI
    lane. The make targets declare real file dependencies, so the
    build step is a no-op whenever the binaries are fresh
    (build-if-stale). The selftest now includes the PR 9/10
    host_store.cc pool paths: concurrent ParallelFor callers racing
    the single-owner mutex into the TryParallelFor inline fallback,
    with the dispatch tallies (parallel/inline_busy/inline_small)
    asserted exact — under TSAN that is precisely the fn_/done_
    handoff race class that segfaulted before PR 9's owner lock."""

    def test_selftest_runs_clean_under_asan(self, native_build):
        """AddressSanitizer + UBSan sibling: heap/stack violations, leaks
        (the handle registry), and UB must stay at zero."""
        build = subprocess.run(["make", "-C", native_build,
                                "mvt_selftest_asan"],
                               capture_output=True, text=True, timeout=300)
        err = build.stderr.lower()
        if build.returncode != 0 and ("sanitize" in err or "asan" in err):
            pytest.skip(f"toolchain lacks ASan: {build.stderr[-200:]}")
        assert build.returncode == 0, build.stderr[-2000:]
        env = dict(os.environ, MVT_HOST_STORE_THREADS="8")
        result = subprocess.run(
            [os.path.join(native_build, "mvt_selftest_asan")],
            capture_output=True, text=True, timeout=240, env=env)
        assert result.returncode == 0, result.stdout + result.stderr
        assert "ALL NATIVE TESTS OK" in result.stdout

    def test_selftest_runs_clean_under_tsan(self, native_build):
        """The whole native runtime (actors, mt_queue, BSP protocol, C API
        worker threads) under ThreadSanitizer — the reference shipped no
        sanitizer builds (SURVEY §5: race detection 'none'); any data race
        fails this test (TSAN exits nonzero and prints WARNING)."""
        build = subprocess.run(["make", "-C", native_build,
                                "mvt_selftest_tsan"],
                               capture_output=True, text=True, timeout=300)
        err = build.stderr.lower()
        if build.returncode != 0 and ("tsan" in err or "sanitize" in err):
            # "unrecognized ... '-fsanitize=thread'" / "not supported for
            # this target" / missing libtsan — environment, not a failure
            pytest.skip(f"toolchain lacks TSAN: {build.stderr[-200:]}")
        assert build.returncode == 0, build.stderr[-2000:]
        # force the host store's worker pool on (hardware_concurrency is 1
        # on this host, which would leave the pool-barrier code — the part
        # TSAN exists to check — unexercised)
        env = dict(os.environ, MVT_HOST_STORE_THREADS="8")
        result = subprocess.run(
            [os.path.join(native_build, "mvt_selftest_tsan")],
            capture_output=True, text=True, timeout=240, env=env)
        assert result.returncode == 0, result.stdout + result.stderr
        assert "WARNING: ThreadSanitizer" not in result.stderr
        assert "ALL NATIVE TESTS OK" in result.stdout


class TestCApiFromPython:
    """The binding path: ctypes over libmultiverso_tpu.so
    (reference binding/python loads libmultiverso the same way)."""

    @pytest.fixture()
    def capi(self, native_build):
        lib = ctypes.CDLL(os.path.join(native_build, "libmultiverso_tpu.so"))
        argc = ctypes.c_int(1)
        argv = (ctypes.c_char_p * 1)(b"prog")
        lib.MV_Init(ctypes.byref(argc), argv)
        yield lib
        lib.MV_ShutDown()

    def test_array_roundtrip(self, capi):
        handle = ctypes.c_void_p()
        capi.MV_NewArrayTable(10, ctypes.byref(handle))
        data = np.arange(10, dtype=np.float32)
        ptr = data.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
        capi.MV_AddArrayTable(handle, ptr, 10)
        out = np.zeros(10, np.float32)
        capi.MV_GetArrayTable(handle,
                              out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                              10)
        np.testing.assert_allclose(out, data)

    def test_matrix_rows(self, capi):
        handle = ctypes.c_void_p()
        capi.MV_NewMatrixTable(6, 3, ctypes.byref(handle))
        deltas = np.ones((2, 3), np.float32)
        ids = np.array([1, 4], np.int32)
        capi.MV_AddMatrixTableByRows(
            handle, deltas.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), 6,
            ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int)), 2)
        out = np.zeros((2, 3), np.float32)
        capi.MV_GetMatrixTableByRows(
            handle, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), 6,
            ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int)), 2)
        np.testing.assert_allclose(out, 1.0)

    def test_world_introspection(self, capi):
        assert capi.MV_NumWorkers() == 1
        assert capi.MV_WorkerId() == 0

    def test_store_load_table(self, capi, tmp_path):
        """MV_StoreTable/MV_LoadTable: native-client persistence over the
        native stream layer (extension — the reference C ABI has none)."""
        handle = ctypes.c_void_p()
        capi.MV_NewArrayTable(6, ctypes.byref(handle))
        data = np.arange(6, dtype=np.float32)
        fptr = ctypes.POINTER(ctypes.c_float)
        capi.MV_AddArrayTable(handle, data.ctypes.data_as(fptr), 6)
        uri = str(tmp_path / "t.bin").encode()
        assert capi.MV_StoreTable(handle, uri) == 0
        capi.MV_AddArrayTable(handle, data.ctypes.data_as(fptr), 6)  # diverge
        assert capi.MV_LoadTable(handle, uri) == 0
        out = np.zeros(6, np.float32)
        capi.MV_GetArrayTable(handle, out.ctypes.data_as(fptr), 6)
        np.testing.assert_allclose(out, data)
        assert capi.MV_LoadTable(handle, b"hdfs://h/p") == -1


class TestCApiMeshBackend:
    """The C ABI routed onto the TPU runtime: MV_RegisterBackend installs
    the python bridge, after which native callers' MV_* verbs hit the SAME
    mesh-backed tables the python surface uses (reference src/c_api.cpp
    wraps its real runtime identically; here the vtable is the wrap)."""

    @pytest.fixture()
    def routed(self, native_build):
        import multiverso_tpu as core
        from multiverso_tpu.binding import native_bridge
        lib = ctypes.CDLL(os.path.join(native_build, "libmultiverso_tpu.so"))
        bridge = native_bridge.install(lib)
        assert lib.MV_HasBackend() == 1
        lib.MV_Init(None, None)  # native client's init -> python world
        yield lib, bridge, core
        lib.MV_ShutDown()        # tears the python world down (bridge owns it)
        bridge.uninstall()

    def test_array_verbs_hit_mesh_tables(self, routed):
        lib, bridge, core = routed
        handle = ctypes.c_void_p()
        lib.MV_NewArrayTable(12, ctypes.byref(handle))
        fptr = ctypes.POINTER(ctypes.c_float)
        data = np.arange(12, dtype=np.float32)
        lib.MV_AddArrayTable(handle, data.ctypes.data_as(fptr), 12)
        out = np.zeros(12, np.float32)
        lib.MV_GetArrayTable(handle, out.ctypes.data_as(fptr), 12)
        np.testing.assert_allclose(out, data)
        # the storage behind the ABI is the python world's device table
        import jax
        entry = bridge._tables[0]
        np.testing.assert_allclose(np.asarray(entry.worker.Get()), data)
        raw = entry.server.raw()
        assert isinstance(raw, jax.Array)

    def test_matrix_rows_and_async(self, routed):
        lib, bridge, core = routed
        handle = ctypes.c_void_p()
        lib.MV_NewMatrixTable(8, 4, ctypes.byref(handle))
        fptr = ctypes.POINTER(ctypes.c_float)
        iptr = ctypes.POINTER(ctypes.c_int)
        deltas = np.full((2, 4), 2.0, np.float32)
        ids = np.array([3, 6], np.int32)
        lib.MV_AddAsyncMatrixTableByRows(
            handle, deltas.ctypes.data_as(fptr), 8,
            ids.ctypes.data_as(iptr), 2)
        lib.MV_Barrier()  # drain the async add
        out = np.zeros((2, 4), np.float32)
        lib.MV_GetMatrixTableByRows(handle, out.ctypes.data_as(fptr), 8,
                                    ids.ctypes.data_as(iptr), 2)
        np.testing.assert_allclose(out, 2.0)
        # whole-table view from the python side agrees
        full = np.asarray(bridge._tables[0].worker.Get())
        assert full.shape == (8, 4)
        np.testing.assert_allclose(full[[3, 6]], 2.0)
        np.testing.assert_allclose(full[[0, 1, 2, 4, 5, 7]], 0.0)

    def test_one_row_matrix_keeps_row_verbs(self, routed):
        """MV_NewMatrixTable(1, N) is a real matrix (row-addressable), not
        an array — the vtable carries the kind, it is not inferred."""
        lib, bridge, core = routed
        handle = ctypes.c_void_p()
        lib.MV_NewMatrixTable(1, 5, ctypes.byref(handle))
        fptr = ctypes.POINTER(ctypes.c_float)
        iptr = ctypes.POINTER(ctypes.c_int)
        d = np.full((1, 5), 3.0, np.float32)
        ids = np.array([0], np.int32)
        lib.MV_AddMatrixTableByRows(handle, d.ctypes.data_as(fptr), 5,
                                    ids.ctypes.data_as(iptr), 1)
        out = np.zeros((1, 5), np.float32)
        lib.MV_GetMatrixTableByRows(handle, out.ctypes.data_as(fptr), 5,
                                    ids.ctypes.data_as(iptr), 1)
        np.testing.assert_allclose(out, 3.0)
        # whole-table verbs on the same 1-row matrix also work
        lib.MV_AddMatrixTableAll(handle, d.ctypes.data_as(fptr), 5)
        lib.MV_GetMatrixTableAll(handle, out.ctypes.data_as(fptr), 5)
        np.testing.assert_allclose(out, 6.0)

    def test_store_load_through_backend(self, routed, tmp_path):
        lib, bridge, core = routed
        handle = ctypes.c_void_p()
        lib.MV_NewArrayTable(6, ctypes.byref(handle))
        fptr = ctypes.POINTER(ctypes.c_float)
        data = np.arange(6, dtype=np.float32)
        lib.MV_AddArrayTable(handle, data.ctypes.data_as(fptr), 6)
        uri = str(tmp_path / "mesh_t.bin").encode()
        assert lib.MV_StoreTable(handle, uri) == 0
        lib.MV_AddArrayTable(handle, data.ctypes.data_as(fptr), 6)
        assert lib.MV_LoadTable(handle, uri) == 0
        out = np.zeros(6, np.float32)
        lib.MV_GetArrayTable(handle, out.ctypes.data_as(fptr), 6)
        np.testing.assert_allclose(out, data)

    def test_worlds_stay_separate(self, native_build):
        """Without a registered backend the CPU store serves; registration
        while a world is live is refused."""
        lib = ctypes.CDLL(os.path.join(native_build, "libmultiverso_tpu.so"))
        lib.MV_Init(None, None)  # CPU-store world
        from multiverso_tpu.binding.native_bridge import (MV_BackendVTable,
                                                          NativeBridge)
        try:
            bridge = NativeBridge(lib)
            with pytest.raises(RuntimeError):
                bridge.install()
        finally:
            lib.MV_ShutDown()


class TestNativeReader:
    def test_parse_libsvm(self, native_build):
        from multiverso_tpu import native
        parsed = native.parse_libsvm(b"1 3:0.5 10:2\n0 1:1.5\n")
        assert parsed is not None
        labels, weights, offsets, keys, values = parsed
        assert labels.tolist() == [1, 0]
        assert keys.tolist() == [3, 10, 1]
        np.testing.assert_allclose(values, [0.5, 2.0, 1.5])
        assert offsets.tolist() == [0, 2, 3]

    def test_weighted(self, native_build):
        from multiverso_tpu import native
        labels, weights, offsets, keys, values = native.parse_libsvm(
            b"1:0.25 2:1\n", weighted=True)
        assert labels[0] == 1
        assert weights[0] == pytest.approx(0.25)

    def test_logreg_uses_native_reader(self, native_build, tmp_path):
        """The LR sparse pipeline gives identical samples through both paths."""
        from multiverso_tpu.models.logreg.configure import Configure
        from multiverso_tpu.models.logreg import data as lr_data
        text = "1 3:0.5 7:2.0\n0 1:1.5 9:1.0\n"
        path = tmp_path / "sp.txt"
        path.write_text(text)
        cfg = Configure()
        cfg.input_size = 10
        cfg.sparse = True
        native_samples = list(lr_data.iter_samples(str(path), cfg))
        # force the python path
        from multiverso_tpu import native as native_mod
        orig = native_mod.lib
        native_mod.lib = lambda: None
        try:
            py_samples = list(lr_data.iter_samples(str(path), cfg))
        finally:
            native_mod.lib = orig
        assert len(native_samples) == len(py_samples) == 2
        for (l1, w1, k1, v1), (l2, w2, k2, v2) in zip(native_samples,
                                                      py_samples):
            assert l1 == l2 and w1 == w2
            np.testing.assert_array_equal(k1, k2)
            np.testing.assert_allclose(v1, v2)

    @staticmethod
    def _sentences(path, d, with_native):
        """sentences_from_file over ``d``, through the native tokenizer
        or with the library held away (the python path)."""
        from multiverso_tpu import native as native_mod
        from multiverso_tpu.models.wordembedding import data as we_data
        orig = native_mod.lib
        if not with_native:
            native_mod.lib = lambda: None
        try:
            return [(ids.tolist(), n) for ids, n in
                    we_data.sentences_from_file(str(path), d)]
        finally:
            native_mod.lib = orig

    def test_vocab_tokenizer_matches_python(self, native_build, tmp_path):
        """WE sentence reader: native tokenizer path == python path."""
        from multiverso_tpu.models.wordembedding.dictionary import Dictionary

        def dictionary():   # one each: a dictionary keeps its tokenizer
            d = Dictionary()
            for w in ["the", "cat", "sat", "on", "mat"]:
                d.Insert(w, 10)
            return d

        corpus = tmp_path / "c.txt"
        # mixed line endings: \n, blank line, \r\n (both paths must agree)
        corpus.write_bytes(
            b"the cat sat on the unknown mat\n\nmat cat\r\nsat mat\n")
        with_lib, without = dictionary(), dictionary()
        native_out = self._sentences(corpus, with_lib, True)
        py_out = self._sentences(corpus, without, False)
        assert with_lib.tokenizer() is not None
        assert without._tokenizer is None       # the python path ran
        assert native_out == py_out
        assert len(native_out) == 3  # blank line skipped, OOV filtered

    @pytest.mark.parametrize("words, min_count", [
        pytest.param([("the", 9), ("cat", 8), ("mat", 7)], 1, id="ascii"),
        pytest.param([("caf\u00e9", 9), ("\u732b", 8), ("na\u00efve", 7),
                      ("cafe", 6), ("\U0001f600", 5)], 1, id="non_ascii"),
        pytest.param([("cat", 9), ("ca", 8), ("cats", 7), ("c", 6),
                      ("catsup", 5)], 1, id="prefix_of_another"),
        pytest.param([("a\0b", 9), ("a", 8), ("b", 7)], 1,
                     id="nul_inside_a_word"),
        pytest.param([("rare", 1), ("rarer", 1)], 2,
                     id="empty_after_pruning"),
        pytest.param([("kept", 5), ("gone", 1), ("k", 3)], 2,
                     id="pruned_and_recompacted"),
    ])
    def test_cheap_tokenizer_build_matches_old(self, native_build, tmp_path,
                                               words, min_count):
        """The blob-and-addresses build gives the table, the ids and the
        -1 / -2 sentinels of the build it replaced (a bytes object and a
        ctypes slot a word), and the python path's sentences."""
        import ctypes
        from multiverso_tpu import native
        from multiverso_tpu.models.wordembedding.dictionary import Dictionary

        def dictionary():
            d = Dictionary()
            for w, c in words:
                d.Insert(w, c)
            d.RemoveWordsLessThan(min_count)
            return d

        d = dictionary()
        text = (" ".join(w for w, _ in words) + " unknown\n\n"
                + " ".join(w + "x " + w[:-1] for w, _ in words)
                + "\r\n" + words[0][0]).encode("utf-8")
        corpus = tmp_path / "c.txt"
        corpus.write_bytes(text)
        tok = d.tokenizer()
        if d.Size() == 0:
            assert tok is None
            assert self._sentences(corpus, d, True) == []
            return
        # the old build, as it stood before this tokenizer
        kept = d.words()
        old_bytes = [w.encode("utf-8") for w in kept]
        old_words = (ctypes.c_char_p * len(kept))(*old_bytes)
        old_table = np.empty(tok._cap, np.int64)
        h = native.lib()
        h.MV_BuildVocabHash(old_words, len(kept), old_table, tok._cap)
        np.testing.assert_array_equal(tok._table, old_table)
        old_ids = np.empty(len(text) + 2, np.int32)
        n = h.MV_TokenizeLinesToIds(text, len(text), old_words, len(kept),
                                    old_table, tok._cap, old_ids,
                                    len(old_ids))
        got = tok.tokenize_lines(text)
        np.testing.assert_array_equal(got, old_ids[:n])
        assert -1 in got and -2 in got
        flat = tok.tokenize(text, len(text))
        np.testing.assert_array_equal(flat, got[got != -2])
        if not any("\0" in w for w in kept):
            # (C reads a word up to its NUL; python does not)
            want = [d.GetWordIdx(t) for t in text.decode("utf-8").split()]
            assert flat.tolist() == want
            plain = dictionary()
            assert (self._sentences(corpus, d, True)
                    == self._sentences(corpus, plain, False))
            assert plain._tokenizer is None     # the python path ran

    def test_malformed_input_raises(self, native_build):
        """Malformed tokens must fail the run, not parse as zeros
        (native parser returns -1 -> ValueError)."""
        from multiverso_tpu import native
        with pytest.raises(ValueError):
            native.parse_libsvm(b"1 abc:2\n")
        with pytest.raises(ValueError):
            native.parse_libsvm(b"xyz 1:2\n")


class TestLoaderBuildFailure:
    """A source checkout whose build fails must say so once and run
    without the library — never load whatever ``.so`` is lying in
    ``native/`` (git-ignored, so possibly older than the sources)."""

    def test_failed_build_logs_once_and_never_loads_stale(
            self, native_build, tmp_path, monkeypatch, capfd):
        from multiverso_tpu import native
        # a checkout with a stale library and a Makefile that cannot
        # rebuild it
        shutil.copy(os.path.join(native_build, "libmultiverso_tpu.so"),
                    tmp_path / "libmultiverso_tpu.so")
        (tmp_path / "Makefile").write_text(
            ".PHONY: libmultiverso_tpu.so\n"
            "libmultiverso_tpu.so:\n"
            "\t@echo 'mvt-test: compiler exploded' >&2; exit 1\n")
        monkeypatch.setattr(native, "_NATIVE_DIR", str(tmp_path))
        monkeypatch.setattr(native, "_REPO_LIB_PATH",
                            str(tmp_path / "libmultiverso_tpu.so"))
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_tried", False)
        capfd.readouterr()
        assert native.lib() is None
        assert native.parse_libsvm(b"1 3:0.5\n") is None
        assert native.VocabTokenizer.create(["the", "cat"]) is None
        assert native.crc32c_fn() is None
        err = capfd.readouterr().err
        assert err.count("native runtime build failed") == 1
        assert "mvt-test: compiler exploded" in err
        assert "running without it" in err


class TestKvIndex:
    def _ix(self, cap=1024):
        from multiverso_tpu import native
        if native.lib() is None:
            pytest.skip("native toolchain unavailable")
        return native.KvIndex.create(cap)

    def test_batch_order_assignment_and_dups(self):
        ix = self._ix()
        keys = np.array([50, -3, 50, 7, 2**62, -3], np.int64)
        slots = ix.insert(keys)
        # batch order, duplicates share the first assignment
        assert slots.tolist() == [0, 1, 0, 2, 3, 1]
        assert len(ix) == 4
        # lookup hits what insert assigned; missing -> -1
        got = ix.lookup(np.array([7, 99, -3], np.int64))
        assert got.tolist() == [2, -1, 1]

    def test_growth_keeps_assignments(self):
        ix = self._ix(cap=4)
        keys = np.arange(10_000, dtype=np.int64) * 7 - 31
        slots = ix.insert(keys)
        assert slots.tolist() == list(range(10_000))
        again = ix.lookup(keys)
        np.testing.assert_array_equal(again, slots)

    def test_items_set_items_roundtrip(self):
        ix = self._ix()
        keys = np.array([9, -1, 123456789012345], np.int64)
        ix.insert(keys)
        ks, ss = ix.items()
        order = np.argsort(ss)
        np.testing.assert_array_equal(ks[order], keys)
        ix2 = self._ix()
        ix2.set_items(ks, ss)
        assert len(ix2) == 3
        np.testing.assert_array_equal(ix2.lookup(keys), [0, 1, 2])
        # inserts continue after the loaded slots
        assert ix2.insert(np.array([777], np.int64)).tolist() == [3]
