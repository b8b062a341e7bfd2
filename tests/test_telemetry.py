"""Telemetry subsystem (multiverso_tpu/telemetry/) — PR 2.

Coverage per the issue checklist:

* histogram bucket math (fixed ladder, percentile interpolation, vector
  merge algebra) — pure, no world needed;
* cross-host registry merge in a REAL 2-process gloo world with
  rank-disjoint instruments (union-of-names over fixed-width vectors),
  riding a windowed engine run with ``-stats_interval_s=1`` so the
  periodic reporter and the window-latency / host-vs-device byte
  instruments are exercised end to end;
* trace export round-trip: ``-trace=true`` world -> ``MV_DumpTrace`` ->
  schema-valid Chrome trace JSON holding ONE span tree spanning worker
  verb -> mailbox -> server window;
* the telemetry-off fast path registers NO instruments;
* satellites: Monitor Begin/End thread-safety, the MV_StartProfiler
  double-start guard, Dashboard.Display through the logger, and the
  no-bare-print lint over the package.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from multiverso_tpu.telemetry import metrics, trace
from tests.test_multihost import run_two_process


class TestHistogramMath:
    def test_bucket_index_ladder(self):
        # exact powers of two sit at their bucket's upper bound
        assert metrics.bucket_index(0.0) == 0
        assert metrics.bucket_index(-1.0) == 0
        assert metrics.bucket_index(2.0 ** -20) == 0
        assert metrics.bucket_index(2.0 ** -19) == 1
        assert metrics.bucket_index(1.5 * 2.0 ** -20) == 1
        assert metrics.bucket_index(1.0) == 20
        assert metrics.bucket_index(1e30) == metrics.N_BUCKETS - 1
        lo, hi = metrics.bucket_bounds(metrics.bucket_index(0.003))
        assert lo < 0.003 <= hi

    def test_percentiles_and_totals(self):
        h = metrics.Histogram("t")
        for _ in range(50):
            h.observe(0.001)
        for _ in range(45):
            h.observe(0.1)
        for _ in range(5):
            h.observe(10.0)
        snap = metrics.Histogram._snapshot(h._vector())
        assert snap["count"] == 100
        assert snap["sum"] == pytest.approx(0.05 + 4.5 + 50.0)
        # p50 falls in 0.001's bucket, p90 in 0.1's, p99 in 10.0's —
        # each estimate bounded by its bucket (one-octave error bars)
        for q, v in (("p50", 0.001), ("p90", 0.1), ("p99", 10.0)):
            lo, hi = metrics.bucket_bounds(metrics.bucket_index(v))
            assert lo <= snap[q] <= hi, (q, snap[q], lo, hi)

    def test_vector_merge_is_elementwise_sum(self):
        """The cross-host merge contract: adding two ranks' fixed-width
        vectors must equal observing both streams on one histogram."""
        a, b, both = (metrics.Histogram("a"), metrics.Histogram("b"),
                      metrics.Histogram("ab"))
        for v in (0.002, 0.004, 1.5):
            a.observe(v)
            both.observe(v)
        for v in (0.004, 30.0):
            b.observe(v)
            both.observe(v)
        merged = np.asarray(a._vector()) + np.asarray(b._vector())
        snap = metrics.Histogram._snapshot(merged)
        expect = metrics.Histogram._snapshot(both._vector())
        assert snap == expect

    def test_empty_histogram(self):
        snap = metrics.Histogram._snapshot(metrics.Histogram("e")._vector())
        assert snap["count"] == 0 and snap["p50"] == 0.0


class TestRegistry:
    def test_lazy_create_and_type_conflict(self):
        from multiverso_tpu.utils.log import FatalError
        metrics._reset_for_tests()
        c = metrics.counter("t.reg.c")
        c.inc(3)
        assert metrics.counter("t.reg.c") is c
        assert metrics.snapshot()["t.reg.c"]["value"] == 3
        with pytest.raises(FatalError):
            metrics.histogram("t.reg.c")
        metrics._reset_for_tests()

    def test_gauge_set_inc_dec(self):
        metrics._reset_for_tests()
        g = metrics.gauge("t.reg.g")
        g.set(5)
        g.inc(2)
        g.dec()
        assert metrics.snapshot()["t.reg.g"]["value"] == 6
        metrics._reset_for_tests()

    def test_merged_snapshot_single_process_identity(self):
        metrics._reset_for_tests()
        metrics.counter("t.m.c").inc(2)
        metrics.histogram("t.m.h").observe(0.5)
        metrics.max_gauge("t.m.mg").set(7)
        merged = metrics.merged_snapshot()
        assert merged["t.m.c"]["value"] == 2
        assert merged["t.m.h"]["count"] == 1
        assert merged["t.m.mg"]["value"] == 7
        metrics._reset_for_tests()


class TestTelemetryOffFastPath:
    def test_no_instruments_registered(self):
        """-telemetry=false: driving real verbs through a world must
        leave the registry EMPTY (instrument lookups return the shared
        no-op), so the off fast path costs nothing to snapshot."""
        import multiverso_tpu as mv
        from multiverso_tpu.tables import MatrixTableOption
        metrics._reset_for_tests()
        mv.MV_Init(["-telemetry=false"])
        try:
            t = mv.MV_CreateTable(MatrixTableOption(num_rows=32,
                                                    num_cols=4))
            ids = np.arange(4, dtype=np.int32)
            t.AddRows(ids, np.ones((4, 4), np.float32))
            t.GetRows(ids)
            assert metrics.snapshot() == {}
            assert mv.MV_MetricsSnapshot() == {}
        finally:
            mv.MV_ShutDown()

    def test_null_instrument_is_inert(self):
        n = metrics.NULL
        n.inc()
        n.dec()
        n.set(3)
        n.observe(1.0)
        assert n.value == 0.0


class TestTraceExport:
    def test_chrome_trace_roundtrip_span_tree(self, tmp_path):
        """-trace=true world -> MV_DumpTrace -> schema-valid Chrome
        trace JSON with ONE span tree spanning worker verb -> mailbox
        (flow events) -> server window."""
        import multiverso_tpu as mv
        from multiverso_tpu.tables import MatrixTableOption
        trace._reset_for_tests()
        mv.MV_Init(["-trace=true"])
        try:
            t = mv.MV_CreateTable(MatrixTableOption(num_rows=32,
                                                    num_cols=4))
            ids = np.arange(4, dtype=np.int32)
            t.AddRows(ids, np.ones((4, 4), np.float32))
            t.GetRows(ids)
            path = str(tmp_path / "trace.json")
            assert mv.MV_DumpTrace(path) == path
        finally:
            mv.MV_ShutDown()
        data = json.load(open(path))
        events = data["traceEvents"]
        assert isinstance(events, list) and events
        for ev in events:   # Chrome trace-event schema
            assert {"name", "ph", "pid", "tid"} <= set(ev), ev
            assert ev["ph"] in ("X", "s", "f", "M"), ev
            if ev["ph"] != "M":     # metadata records carry no timestamp
                assert isinstance(ev["ts"], (int, float)), ev
            if ev["ph"] == "X":
                assert ev["dur"] >= 0
                assert {"trace_id", "span_id",
                        "parent_id"} <= set(ev["args"])
        by_name = {}
        for ev in events:
            by_name.setdefault(ev["name"], []).append(ev)
        worker = by_name["worker.add"][0]
        tid = worker["args"]["trace_id"]
        # the dispatch span picked the worker's context up off the
        # message (cross-thread parenting)...
        dispatch = [e for e in by_name["actor.server.dispatch"]
                    if e["args"]["trace_id"] == tid
                    and e["args"]["parent_id"] == worker["args"]["span_id"]]
        assert dispatch, "dispatch span not parented to the worker verb"
        assert dispatch[0]["tid"] != worker["tid"], \
            "worker and engine spans should sit on different threads"
        # ...and the server window nests under the dispatch
        window = [e for e in by_name["server.window"]
                  if e["args"]["trace_id"] == tid]
        assert window, "server window span missing from the verb's tree"
        # the mailbox hop has a flow arrow: s on the worker thread,
        # f on the engine thread, same id
        starts = {e["id"] for e in events if e["ph"] == "s"}
        ends = {e["id"] for e in events if e["ph"] == "f"}
        assert worker["args"]["span_id"] in starts & ends

    def test_trace_off_records_nothing(self):
        trace._reset_for_tests()
        with trace.span("t.off"):
            pass
        assert len(trace.to_chrome_trace()["traceEvents"]) == 1  # meta only


class TestHostSpans:
    """PR 23: the spans and counters inside the app loop, the engine's
    single-process window and the device-plane verbs. Counts and
    structure only: no wall-clock threshold."""

    PLANES = {"host": [], "host_pipeline": ["-is_pipeline", "1"],
              "device_plane": ["-device_plane", "1"],
              "device_pairs": ["-device_pairs", "1"]}
    #: worker.we.* children of worker.we.block, by plane (a prefetched
    #: block's fetch is a sibling: train() waits for it after the block
    #: before; the fused program has no row fetch or push)
    BLOCK_CHILDREN = {
        "host": {"fetch", "upload", "dispatch", "push"},
        "host_pipeline": {"upload", "dispatch", "push"},
        "device_plane": {"fetch", "upload", "dispatch", "push"},
        "device_pairs": {"upload", "dispatch"}}

    @staticmethod
    def _spans():
        return [e for e in trace.to_chrome_trace()["traceEvents"]
                if e["ph"] == "X"]

    @staticmethod
    def _children(spans, parent):
        return [e for e in spans
                if e["args"]["parent_id"] == parent["args"]["span_id"]]

    def _train(self, tmp_path, plane, trace_on):
        """A tiny WordEmbedding job in one plane. -> (app, wall of
        train() in us, the ring's spans, the metrics snapshot)."""
        import multiverso_tpu as mv
        from multiverso_tpu.models.wordembedding.distributed import (
            DistributedWordEmbedding)
        from multiverso_tpu.models.wordembedding.option import Option
        rng = np.random.default_rng(0)
        words = [f"w{i}" for i in range(120)]
        corpus = tmp_path / "corpus.txt"
        corpus.write_text("".join(
            " ".join(rng.choice(words, 12)) + "\n" for _ in range(200)))
        opt = Option.parse_args(
            ["-train_file", str(corpus), "-output", str(tmp_path / "v.bin"),
             "-size", "8", "-min_count", "1", "-data_block_size", "6000",
             "-epoch", "1", "-is_pipeline", "0", "-pair_batch", "256",
             "-use_adagrad", "1"] + self.PLANES[plane])
        trace._reset_for_tests()
        metrics._reset_for_tests()
        mv.MV_Init(["-trace=true"] if trace_on else [])
        we = DistributedWordEmbedding(opt)
        try:
            we.prepare()
            t0 = time.perf_counter()
            we.train()
            wall_us = (time.perf_counter() - t0) * 1e6
            return we, wall_us, self._spans(), metrics.snapshot()
        finally:
            we.close()
            mv.MV_ShutDown()

    @pytest.mark.parametrize("plane", list(PLANES))
    def test_train_leaves_one_tree_a_block(self, tmp_path, plane):
        _, wall_us, spans, snap = self._train(tmp_path, plane, True)
        named = lambda n: [e for e in spans if e["name"] == n]  # noqa: E731
        blocks = named("worker.we.block")
        assert len(blocks) >= 3, "the corpus should make several blocks"
        assert len(blocks) == snap["we.blocks"]["value"]
        assert sorted(b["args"]["block"] for b in blocks) == list(
            range(len(blocks)))
        assert snap["we.pop_wait_s"]["count"] == len(blocks) + 1
        assert len(named("worker.we.pop_wait")) == len(blocks) + 1
        made = {e["args"]["span_id"]: e
                for e in named("worker.we.load.make_block")}
        assert len(made) == len(blocks)
        by_id = {e["args"]["span_id"]: e for e in spans}
        for b in blocks:
            parent = made.get(b["args"]["parent_id"])
            assert parent is not None, "block not parented to a make_block"
            assert parent["tid"] != b["tid"], "loader and loop share a thread"
            kids = {e["name"] for e in self._children(spans, b)}
            want = set(self.BLOCK_CHILDREN[plane])
            if plane == "host_pipeline" and b["args"]["block"] == 0:
                want.add("fetch")       # nothing prefetched the first
            assert {k[len("worker.we."):] for k in kids
                    if k.startswith("worker.we.")} == want
        # each block's harvest hangs off the same make_block span: one
        # tree from the loader to the harvest
        harvests = named("worker.we.harvest")
        assert len(harvests) == len(blocks)
        assert {h["args"]["parent_id"] for h in harvests} == set(made)
        assert {h["args"]["trace_id"] for h in harvests} == {
            b["args"]["trace_id"] for b in blocks}
        if plane == "host_pipeline":
            fetches = [e for e in named("worker.we.fetch")
                       if e["args"]["parent_id"] in made]
            assert len(fetches) == len(blocks) - 1   # all but the first
        if plane == "device_plane":
            for f in named("server.table.device_fetch"):
                assert by_id[f["args"]["parent_id"]]["name"] == \
                    "worker.we.fetch"
        # the loop's three serial parts fit inside train()'s wall
        serial = sum(e["dur"] for n in ("worker.we.pop_wait",
                                        "worker.we.block",
                                        "worker.we.harvest")
                     for e in named(n))
        assert serial <= wall_us
        reads = named("worker.we.load.read")
        assert len(reads) == len(blocks) + 1     # the last finds the end
        # the tokenizer's one build was prepare()'s, never the loader's
        loader_tids = {e["tid"] for e in reads}
        builds = named("worker.we.load.tokenizer")
        assert len(builds) <= 1     # none without the native library
        assert not loader_tids & {e["tid"] for e in builds}

    @pytest.mark.parametrize("plane", ["host", "device_pairs"])
    def test_prepare_sets_its_gauges(self, tmp_path, plane):
        _, _, _, snap = self._train(tmp_path, plane, False)
        for part in ("dictionary", "tokenizer", "sampler", "world",
                     "tables", "trainer"):
            g = snap[f"we.prepare.{part}_s"]
            assert g["type"] == "gauge" and g["value"] >= 0.0
        made = snap["table.create_s"]
        assert made["count"] == 4       # input, output, two AdaGrad tables
        assert 0.0 < made["sum"] <= snap["we.prepare.tables_s"]["value"]
        # the tokenizer's build is a part of the dictionary's lap
        assert (snap["we.prepare.tokenizer_s"]["value"]
                <= snap["we.prepare.dictionary_s"]["value"])

    @staticmethod
    def _window_drive(mv):
        """Two Adds and two Gets in ONE engine window (one batched
        envelope), then a lone blocking Add."""
        from multiverso_tpu.tables import MatrixTableOption
        from multiverso_tpu.tables.base import submit_multi
        t = mv.MV_CreateTable(MatrixTableOption(num_rows=64, num_cols=4))
        ids = np.arange(8, dtype=np.int32)
        ones = np.ones((8, 4), np.float32)
        got = submit_multi(
            [(t, "A", {"row_ids": ids, "values": ones}),
             (t, "A", {"row_ids": ids + 8, "values": ones}),
             (t, "G", {"row_ids": ids}),
             (t, "G", {"row_ids": ids + 8})]).Wait()
        t.AddRows(ids, ones)
        return t, got

    def test_single_process_window_tree(self):
        import multiverso_tpu as mv
        trace._reset_for_tests()
        mv.MV_Init(["-trace=true"])
        try:
            t, got = self._window_drive(mv)
            np.testing.assert_array_equal(got[2], np.ones((8, 4)))
            spans = self._spans()
        finally:
            mv.MV_ShutDown()
        window = [e for e in spans if e["name"] == "server.window"
                  and e["args"]["verbs"] == 4]
        assert len(window) == 1
        window = window[0]
        kids = sorted(self._children(spans, window), key=lambda e: e["ts"])
        assert [k["name"] for k in kids] == [
            "server.window.form",
            "server.table.add_run.merge", "server.table.add_run.dispatch",
            "server.table.get.prepare", "server.table.get.dispatch",
            "server.table.get.prepare", "server.table.get.dispatch",
            "server.window.finalize"]
        assert kids[1]["args"]["adds"] == 2
        for k in kids:      # nested in time as well as in the tree
            assert window["ts"] <= k["ts"]
            assert k["ts"] + k["dur"] <= window["ts"] + window["dur"] + 1
        # admission is the window's elder sibling on the engine's thread
        admits = [e for e in spans if e["name"] == "server.window.admit"
                  and e["args"]["parent_id"] == window["args"]["parent_id"]]
        assert len(admits) == 1
        assert admits[0]["ts"] + admits[0]["dur"] <= window["ts"] + 1
        assert admits[0]["tid"] == window["tid"]
        # the blocking Add waited for its reply on the caller's thread
        waits = [e for e in spans if e["name"] == "worker.wait"]
        assert waits and all(w["tid"] == threading.get_ident()
                             and w["args"]["table_id"] == t.table_id
                             for w in waits)
        assert waits[-1]["tid"] != window["tid"]
        # a lone Add is a run of one: the same two names, no form of many
        lone = [e for e in spans if e["name"] == "server.window"
                and e["args"]["verbs"] == 1]
        assert lone
        names = [k["name"] for k in self._children(spans, lone[-1])]
        assert names.count("server.table.add_run.dispatch") == 1
        assert "server.table.add_run.merge" in names

    @pytest.mark.parametrize("verb", ["device_fetch", "device_apply"])
    def test_device_plane_verb_spans(self, verb):
        import multiverso_tpu as mv
        from multiverso_tpu.tables import MatrixTableOption
        trace._reset_for_tests()
        mv.MV_Init(["-trace=true"])
        try:
            t = mv.MV_CreateTable(MatrixTableOption(num_rows=64,
                                                    num_cols=4))
            ids = np.arange(0, 16, 2, dtype=np.int32)
            srv = t.server()
            trace.clear()
            if verb == "device_fetch":
                rows = srv.device_fetch_rows(ids)
                assert rows.shape == (8, 4)
            else:
                srv.device_apply_rows(ids, np.ones((8, 4), np.float32))
                np.testing.assert_array_equal(t.GetRows(ids),
                                              np.ones((8, 4)))
            spans = self._spans()
        finally:
            mv.MV_ShutDown()
        top = [e for e in spans if e["name"] == f"server.table.{verb}"]
        assert len(top) == 1 and top[0]["args"]["table_id"] == t.table_id
        kids = sorted(self._children(spans, top[0]), key=lambda e: e["ts"])
        assert [k["name"] for k in kids] == [
            f"server.table.{verb}.prepare", f"server.table.{verb}.dispatch"]
        assert sum(k["dur"] for k in kids) <= top[0]["dur"]

    @pytest.mark.parametrize("drive", ["train", "window", "device_verbs"])
    def test_trace_off_leaves_the_ring_empty(self, tmp_path, drive):
        """-trace off: the same drives record nothing, and every span()
        is the one shared no-op object (no allocation per call)."""
        import multiverso_tpu as mv
        from multiverso_tpu.tables import MatrixTableOption
        if drive == "train":
            _, _, spans, snap = self._train(tmp_path, "device_plane", False)
            assert snap["we.blocks"]["value"] >= 3  # counters stay on
        else:
            trace._reset_for_tests()
            mv.MV_Init([])
            try:
                if drive == "window":
                    self._window_drive(mv)
                else:
                    t = mv.MV_CreateTable(MatrixTableOption(num_rows=64,
                                                            num_cols=4))
                    ids = np.arange(8, dtype=np.int32)
                    t.server().device_apply_rows(
                        ids, np.ones((8, 4), np.float32))
                    t.server().device_fetch_rows(ids)
                assert trace.span("worker.we.block") is trace.span(
                    "server.window.form", args=None)
                spans = self._spans()
            finally:
                mv.MV_ShutDown()
        assert spans == []
        assert trace.span("server.table.device_fetch") is trace._NULL_SPAN

    # -- PR 35: the table layer's crossings into and out of the device ------

    #: path -> (the span the crossings hang under, its children in order
    #: as (suffix, program or None), the counters' steps). ``b`` is the id
    #: bucket of the 40 ids the drives use, ``row`` a logical row's bytes.
    N, B, COLS = 40, 64, 4

    @classmethod
    def _crossing_cases(cls):
        n, b, row = cls.N, cls.B, cls.COLS * 4
        place, wait, take = (".place", None), (".wait", None), (".take", None)
        call = lambda program: (".call", program)  # noqa: E731
        return {
            # a fetch of n ids at bucket b: one copy of 4 b bytes and one
            # call, two when n != b
            "fetch_at_bucket": (
                "server.table.device_fetch.dispatch",
                [place, call("_gather_rows")],
                dict(h2d_copies=1, h2d_bytes=4 * b, calls=1)),
            "fetch_under_bucket": (
                "server.table.device_fetch.dispatch",
                [place, call("_gather_rows"), call("slice")],
                dict(h2d_copies=1, h2d_bytes=4 * b, calls=2)),
            # the table's first apply: the option's five scalars in one
            # put, then the host delta at its exact size, then the ids
            "apply_first_option": (
                "server.table.device_apply.dispatch",
                [place, place, place, call("_pad_row_batch"),
                 call("_update_rows")],
                dict(h2d_copies=7, h2d_bytes=20 + n * row + 4 * b,
                     calls=2)),
            "apply_host_delta": (
                "server.table.device_apply.dispatch",
                [place, place, call("_pad_row_batch"),
                 call("_update_rows")],
                dict(h2d_copies=2, h2d_bytes=n * row + 4 * b, calls=2)),
            # a device delta that is its bucket: ids alone cross
            "apply_device_delta_at_bucket": (
                "server.table.device_apply.dispatch",
                [place, call("_update_rows")],
                dict(h2d_copies=1, h2d_bytes=4 * b, calls=1)),
            # a short device delta: the pad program is a call of its own
            "apply_device_delta_short": (
                "server.table.device_apply.dispatch",
                [place, call("_pad_row_batch"), call("_update_rows")],
                dict(h2d_copies=1, h2d_bytes=4 * b, calls=2)),
            # repeats: ids (at the distinct count's power of two, 32) and
            # inverse map (at the positions' bucket) in ONE put
            "apply_repeats_at_bucket": (
                "server.table.device_apply.dispatch",
                [place, call("_merged_add_rows")],
                dict(h2d_copies=2, h2d_bytes=4 * 32 + 4 * b, calls=1)),
            "apply_repeats_short": (
                "server.table.device_apply.dispatch",
                [place, call("_pad_row_batch"), call("_merged_add_rows")],
                dict(h2d_copies=2, h2d_bytes=4 * 32 + 4 * b, calls=2)),
            # a merged run of two Adds of n rows: ids (power of two over
            # 2 n distinct), stacked deltas, inverse map
            "add_run": (
                "server.table.add_run.dispatch",
                [place, place, place, call("_merged_add_rows")],
                dict(h2d_copies=3,
                     h2d_bytes=4 * 128 + 2 * n * row + 4 * 2 * n, calls=1)),
            "lone_add": (
                "server.table.add_run.dispatch",
                [place, place, call("_pad_row_batch"),
                 call("_update_rows")],
                dict(h2d_copies=2, h2d_bytes=4 * b + n * row, calls=2)),
            # a host Get: ONE call; the gather's bucket comes back with
            # its pad (the host cuts it by a view), waited for and taken
            # in the window's finalize
            "host_get": (
                "server.table.get.dispatch",
                [place, call("_gather_rows")],
                dict(h2d_copies=1, h2d_bytes=4 * b, calls=1,
                     d2h_copies=1, d2h_bytes=b * row)),
            "host_get_finalize": (
                "server.window.finalize", [wait, take],
                dict(h2d_copies=1, h2d_bytes=4 * b, calls=1,
                     d2h_copies=1, d2h_bytes=b * row)),
            # a sparse Get: the whole bucket comes back
            "sparse_get": (
                "server.table.sparse.get.read",
                [place, call("_read_stale"), (".unique", None), wait, take],
                dict(h2d_copies=1, h2d_bytes=4 * b, calls=1,
                     d2h_copies=1, d2h_bytes=b * row)),
        }

    def _crossing_drive(self, mv, path):
        """Warm the path's verb (programs compiled, the option's scalars
        kept), then run it once between two snapshots. -> (spans of the
        one run, how far each table.device.* counter moved, the
        ``block_until_ready`` calls inside it)."""
        import jax
        import jax.numpy as jnp
        from multiverso_tpu.tables import (MatrixTableOption,
                                           SparseMatrixTableOption)
        from multiverso_tpu.tables.base import submit_multi
        from multiverso_tpu.updaters.base import GetOption
        n, b, cols = self.N, self.B, self.COLS
        sparse = path == "sparse_get"
        make = SparseMatrixTableOption if sparse else MatrixTableOption
        t = mv.MV_CreateTable(make(num_rows=256, num_cols=cols))
        srv = t.server()
        ids = np.arange(0, 2 * n, 2, dtype=np.int32)
        at_bucket = np.arange(b, dtype=np.int32)
        repeats = np.concatenate([ids[:24], ids[:24], ids[:16]])  # 64 / 24
        ones = lambda k: np.ones((k, cols), np.float32)  # noqa: E731
        drives = {
            "fetch_at_bucket": lambda: srv.device_fetch_rows(at_bucket),
            "fetch_under_bucket": lambda: srv.device_fetch_rows(ids),
            "apply_first_option": lambda: srv.device_apply_rows(
                ids, ones(n)),
            "apply_host_delta": lambda: srv.device_apply_rows(ids, ones(n)),
            "apply_device_delta_at_bucket": lambda: srv.device_apply_rows(
                at_bucket, jnp.ones((b, cols), jnp.float32)),
            "apply_device_delta_short": lambda: srv.device_apply_rows(
                ids, jnp.ones((n, cols), jnp.float32)),
            "apply_repeats_at_bucket": lambda: srv.device_apply_rows(
                repeats, jnp.ones((b, cols), jnp.float32)),
            "apply_repeats_short": lambda: srv.device_apply_rows(
                repeats[:50], jnp.ones((50, cols), jnp.float32)),
            "add_run": lambda: submit_multi(
                [(t, "A", {"row_ids": ids, "values": ones(n)}),
                 (t, "A", {"row_ids": ids + 1, "values": ones(n)})]).Wait(),
            "lone_add": lambda: t.AddRows(ids, ones(n)),
            "host_get": lambda: t.GetRows(ids),
            "host_get_finalize": lambda: t.GetRows(ids),
        }
        if sparse:
            # worker 1 finds the n rows worker 0 added since its last Get
            def drive():
                t.AddRows(ids, ones(n))
                snap = metrics.snapshot()
                trace.clear()
                got_ids, rows = t.Get(GetOption(worker_id=1))
                assert got_ids.shape == (n,) and rows.shape == (n, cols)
                return snap
        else:
            def drive():
                snap = metrics.snapshot()
                trace.clear()
                drives[path]()
                return snap
        if path != "apply_first_option":
            drive()
        real, blocked = jax.block_until_ready, []
        jax.block_until_ready = lambda x: (blocked.append(1), real(x))[1]
        try:
            before = drive()
        finally:
            jax.block_until_ready = real
        after = metrics.snapshot()
        names = ("h2d_copies", "h2d_bytes", "calls", "d2h_copies",
                 "d2h_bytes")
        moved = {k: after.get(f"table.device.{k}", {}).get("value", 0.0)
                 - before.get(f"table.device.{k}", {}).get("value", 0.0)
                 for k in names}
        return self._spans(), {k: v for k, v in moved.items() if v}, \
            len(blocked)

    @pytest.mark.parametrize("path", [
        "fetch_at_bucket", "fetch_under_bucket", "apply_first_option",
        "apply_host_delta", "apply_device_delta_at_bucket",
        "apply_device_delta_short", "apply_repeats_at_bucket",
        "apply_repeats_short", "add_run", "lone_add", "host_get",
        "host_get_finalize", "sparse_get"])
    @pytest.mark.parametrize("trace_on", [True, False],
                             ids=["trace_on", "trace_off"])
    def test_device_crossings(self, path, trace_on):
        """Every copy in, program call and copy back of a table verb is a
        counter step whatever the flags, and with -trace on a leaf span
        named after the span it runs in, under it, in order."""
        import multiverso_tpu as mv
        parent_name, want_kids, want_moved = self._crossing_cases()[path]
        trace._reset_for_tests()
        metrics._reset_for_tests()
        mv.MV_Init(["-num_workers=2"] + (["-trace=true"] if trace_on
                                         else []))
        try:
            spans, moved, blocked = self._crossing_drive(mv, path)
            null = trace.child(".place") is trace._NULL_SPAN
        finally:
            mv.MV_ShutDown()
        assert moved == {k: float(v) for k, v in want_moved.items()}
        waits = want_moved.get("d2h_copies", 0)     # one a copy back
        if not trace_on:
            # no span object, no synchronisation the verb did not have
            assert spans == [] and null and blocked == 0
            return
        assert not null and blocked == waits
        parents = [e for e in spans if e["name"] == parent_name]
        assert len(parents) == 1
        kids = sorted(self._children(spans, parents[0]),
                      key=lambda e: e["ts"])
        assert [(k["name"], k["args"].get("program")) for k in kids] == [
            (parent_name + suffix, program) for suffix, program in want_kids]
        assert all(k["cat"] == "server" and k["tid"] == parents[0]["tid"]
                   for k in kids)
        # .wait and .take exist nowhere but where a copy comes back
        ends = [e["name"].rsplit(".", 1)[1] for e in spans
                if e["cat"] == "server"]    # not the caller's worker.wait
        assert ends.count("wait") == ends.count("take") == moved.get(
            "d2h_copies", 0)
        if path.startswith("apply_repeats"):
            prepare = [e for e in spans if e["name"]
                       == "server.table.device_apply.prepare"]
            assert [k["name"][len(prepare[0]["name"]):] for k in sorted(
                self._children(spans, prepare[0]), key=lambda e: e["ts"])
            ] == [".unique", ""]    # the combine keeps its PR 33 name
            assert [e["name"] for e in spans].count(
                "server.table.device_apply.combine") == 1

    def test_take_steps_a_verbs_own_counter_too(self):
        """``crossing.take(..., also=name)``: the path that brings a
        copy back steps its verb's counter from the same helper."""
        import jax.numpy as jnp
        from multiverso_tpu.tables import crossing
        metrics._reset_for_tests()
        host = crossing.take(jnp.ones((8, 4), jnp.float32),
                             also="table.device_apply.d2h_bytes")
        snap = metrics.snapshot()
        assert host.shape == (8, 4)
        assert snap["table.device_apply.d2h_bytes"]["value"] == 128.0
        assert snap["table.device.d2h_bytes"]["value"] == 128.0
        assert snap["table.device.d2h_copies"]["value"] == 1.0

    def test_child_outside_any_span_is_named_an_orphan(self):
        from multiverso_tpu.utils.configure import SetCMDFlag
        trace._reset_for_tests()
        SetCMDFlag("trace", True)
        try:
            with trace.child(".take"):
                pass
            with trace.span("worker.x", cat="worker"):
                with trace.child(".call", {"program": "p"}) as ctx:
                    assert trace.current_ctx() == ctx
        finally:
            SetCMDFlag("trace", False)
        spans = self._spans()
        assert [(e["name"], e["cat"]) for e in spans] == [
            (trace.ORPHAN + ".take", "server"), ("worker.x.call", "worker"),
            ("worker.x", "worker")]
        assert spans[1]["args"]["parent_id"] == spans[2]["args"]["span_id"]
        assert spans[1]["args"]["program"] == "p"
        trace._reset_for_tests()

    def test_a_span_with_the_bridge_on_counts_its_work(self):
        """What a span pays while a trace runs, counted and not timed:
        one annotation entered and left, one ring entry that is the span
        itself (its event's dicts are built at export), and neither an
        import nor a lock in ``__enter__`` / ``__exit__``."""
        import dis
        from multiverso_tpu.utils.configure import SetCMDFlag

        class Annotation:
            made, entered, left = [], 0, 0

            def __init__(self, name):
                Annotation.made.append(name)

            def __enter__(self):
                Annotation.entered += 1

            def __exit__(self, *exc):
                Annotation.left += 1

        trace._reset_for_tests()
        SetCMDFlag("trace", True)
        trace._annotation = Annotation
        try:
            for _ in range(100):
                with trace.span("server.a", cat="server"):
                    with trace.child(".call"):
                        pass
        finally:
            SetCMDFlag("trace", False)
            trace.set_xplane(False)
        assert Annotation.entered == Annotation.left == 200
        assert Annotation.made == ["server.a", "server.a.call"] * 100
        assert len(trace._events) == 200
        assert all(type(e) is trace._Span for e in trace._events)
        ids = {e._ctx.span_id for e in trace._events}
        assert len(ids) == 200
        for fn in (trace._Span.__enter__, trace._Span.__exit__):
            code = {(i.opname, i.argval) for i in dis.get_instructions(fn)}
            assert not any(op == "IMPORT_NAME" for op, _ in code)
            assert not any("lock" in str(arg).lower() for _, arg in code)
        assert len(self._spans()) == 200     # export makes the events
        trace._reset_for_tests()


class TestProfilerGuard:
    def test_double_start_checks_and_stop_without_start_noop(self, tmp_path):
        import multiverso_tpu as mv
        from multiverso_tpu.utils.log import FatalError
        mv.MV_StopProfiler()        # no active trace: logged no-op
        mv.MV_StartProfiler(str(tmp_path))
        try:
            with pytest.raises(FatalError, match="one trace at a time"):
                mv.MV_StartProfiler(str(tmp_path))
        finally:
            mv.MV_StopProfiler()
        mv.MV_StopProfiler()        # unmatched again: still a no-op
        # the guard must not wedge the next legitimate trace
        mv.MV_StartProfiler(str(tmp_path))
        mv.MV_StopProfiler()


class TestMonitorThreadSafety:
    def test_concurrent_begin_end_regions(self):
        """Two threads running Begin/End regions concurrently must not
        corrupt each other (the old single shared _begin slot lost
        regions and mis-timed the rest)."""
        from multiverso_tpu.utils.dashboard import Monitor
        mon = Monitor("t.mt", register=False)
        N = 200

        def run():
            for _ in range(N):
                mon.Begin()
                mon.End()

        ts = [threading.Thread(target=run) for _ in range(2)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert mon.count == 2 * N
        assert mon.elapse_ms >= 0

    def test_unmatched_end_is_noop_and_nesting_pairs(self):
        from multiverso_tpu.utils.dashboard import Monitor
        mon = Monitor("t.nest", register=False)
        mon.End()                   # no Begin: ignored
        assert mon.count == 0
        mon.Begin()
        time.sleep(0.002)
        mon.Begin()
        mon.End()                   # inner
        mon.End()                   # outer
        assert mon.count == 2
        assert mon.elapse_ms >= 2   # outer region kept its early start


class TestDashboardThroughLogger:
    def test_display_respects_log_level(self, capsys):
        """Display rides Log.Info now: silenced below the Error level,
        return-string contract intact (the old bare print ignored the
        configured level)."""
        from multiverso_tpu.utils.dashboard import Dashboard, Monitor
        from multiverso_tpu.utils.log import Log, LogLevel
        Dashboard._reset_for_tests()
        Monitor("t.disp").Add(0.001)
        Log.ResetLogLevel(LogLevel.Error)
        try:
            out = Dashboard.Display()
        finally:
            Log.ResetLogLevel(LogLevel.Info)
        assert "t.disp" in out
        captured = capsys.readouterr()
        assert "t.disp" not in captured.err and "t.disp" not in captured.out
        out = Dashboard.Display()
        assert "t.disp" in capsys.readouterr().err
        Dashboard._reset_for_tests()


class TestNoBarePrintLint:
    """Round-16 migration: the PR 2 regex lint now rides the mvlint AST
    framework (multiverso_tpu.analysis.rules.NoBarePrintChecker) — same
    law, but immune to prints split across lines or hidden in strings,
    and suppressible only through the reasoned mv-lint contract. The
    scanned-files pins and the allowlist survive the migration."""

    #: the logger's own sinks are the one legitimate print site
    ALLOW = {os.path.join("utils", "log.py")}

    def test_package_routes_output_through_logger(self):
        from multiverso_tpu.analysis import run_analysis
        from multiverso_tpu.analysis.rules import NoBarePrintChecker
        # the allowlist is part of the law — pin it where it was
        assert set(NoBarePrintChecker.ALLOW) == \
            {rel.replace(os.sep, "/") for rel in self.ALLOW}
        result = run_analysis(rules=["no-bare-print"])
        scanned = result.checkers[0].scanned
        # pin the serving subpackage (round 8) — its output must ride
        # the logger like everything else
        assert any(rel.startswith("serving") for rel in scanned), \
            sorted(scanned)
        # ...and the ops-plane modules (round 9) + the perf-forensics
        # modules (round 11) + the watchdog plane (round 13): the
        # forensics/critpath CLIs, the HTTP handler, the watchdog's
        # alert lines and the ledger all emit text and must ride the
        # logger too
        for need in ("flight.py", "ops.py", "forensics.py",
                     "critpath.py", "align.py", "sketch.py",
                     "watchdog.py", "accounting.py"):
            assert f"telemetry/{need}" in scanned, sorted(scanned)
        # ...and the round-12 shm wire: its waits/errors must ride the
        # logger like every other transport layer
        assert "parallel/shm_wire.py" in scanned, sorted(scanned)
        # ...and the round-16 analysis plane itself (its CLI writes to
        # stdout via sys.stdout.write, never bare print)
        assert "analysis/cli.py" in scanned, sorted(scanned)
        # ...and the round-17 replica plane: the rglob pin — every one
        # of its modules (reader process included, whose stdout is a
        # service surface) must ride the logger
        for need in ("replica.py", "publisher.py", "delta.py",
                     "__init__.py"):
            assert f"replica/{need}" in scanned, sorted(scanned)
        # ...and the round-19 seal + flat-codec modules: the versioned
        # trailer and the serve-protocol framing are failure-reporting
        # surfaces too
        assert "parallel/seal.py" in scanned, sorted(scanned)
        assert "parallel/flat.py" in scanned, sorted(scanned)
        assert not result.findings, (
            "bare print() in the package — route output through "
            "utils/log.py or the telemetry exporters:\n"
            + "\n".join(f.render() for f in result.findings))


_TELEMETRY_2PROC_CHILD = r'''
import json, os, sys, time
rank, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.tables import MatrixTableOption
from multiverso_tpu.telemetry import metrics

mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2", "-stats_interval_s=1", "-trace=true"])
mat = mv.MV_CreateTable(MatrixTableOption(num_rows=256, num_cols=8))
rng = np.random.default_rng(3 + rank)
# windowed burst: fire-and-forget Adds + a draining Get per round
for _ in range(6):
    for _ in range(4):
        mat.AddFireForget(rng.standard_normal((16, 8)).astype(np.float32),
                          row_ids=rng.choice(256, 16,
                                             replace=False).astype(np.int32))
    mat.GetRows(np.arange(8, dtype=np.int32))

# rank-disjoint instruments: the union-of-names merge must carry BOTH
# ranks' names to everyone, with absent ranks contributing zeros
metrics.counter(f"test.only_rank{rank}").inc(rank + 1)
metrics.counter("test.shared").inc(10)
metrics.histogram(f"test.hist_rank{rank}").observe(0.5 * (rank + 1))
metrics.max_gauge("test.maxg").set(5 + rank)   # merge = max, not sum

time.sleep(1.3)            # let the periodic reporter fire at least once
mv.MV_Barrier()            # engines quiesced -> the snapshot collective
snap = mv.MV_MetricsSnapshot()

# both ranks see BOTH rank-disjoint counters with the pushing rank's value
assert snap["test.only_rank0"]["value"] == 1, snap["test.only_rank0"]
assert snap["test.only_rank1"]["value"] == 2, snap["test.only_rank1"]
assert snap["test.shared"]["value"] == 20, snap["test.shared"]
assert snap["test.hist_rank0"]["count"] == 1
assert snap["test.hist_rank1"]["count"] == 1
assert snap["test.maxg"]["value"] == 6, snap["test.maxg"]   # max(5, 6)

# the windowed engine's instruments merged across hosts: window-latency
# histogram with percentiles, and the host-vs-device byte counters
lat = snap["server.window.latency_s"]
assert lat["type"] == "histogram" and lat["count"] >= 2, lat
assert 0 < lat["p50"] <= lat["p99"], lat
assert snap["server.wire.host_bytes"]["value"] > 0
assert snap["server.wire.device_bytes"]["value"] >= 0
assert snap["server.window.exchanges"]["value"] >= 2
assert snap["table.matrix0.add.bytes"]["value"] > 0
assert snap["actor.server.queue_wait_s"]["count"] > 0

# per-rank trace dump: one span tree follows a verb worker -> mailbox
# -> WINDOWED server path (window span + its exchange child)
path = mv.MV_DumpTrace(os.path.join(os.path.dirname(os.path.abspath(
    sys.argv[0])), f"trace_{rank}.json"))
events = json.load(open(path))["traceEvents"]
xs = [e for e in events if e["ph"] == "X"]
worker = [e for e in xs if e["name"] == "worker.add"]
assert worker, "no worker verb spans"
tids = {e["args"]["trace_id"] for e in worker}
windows = [e for e in xs if e["name"] == "server.window"
           and e["args"]["trace_id"] in tids]
assert windows, "no window span in any worker verb's tree"
win_ids = {e["args"]["span_id"] for e in windows}
exchanges = [e for e in xs if e["name"] == "server.window.exchange"
             and e["args"]["parent_id"] in win_ids]
assert exchanges, "window span has no exchange child"

mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} TELEMETRY OK", flush=True)
'''


class TestTwoProcessTelemetry:
    def test_cross_host_merge_and_reporter(self, tmp_path):
        """A 2-proc windowed run with -stats_interval_s=1: the periodic
        reporter emits local snapshot lines through the logger, and
        MV_MetricsSnapshot returns a cross-host-merged snapshot holding
        rank-disjoint instruments (union-of-names), window-latency
        percentiles, and host-vs-device byte counters."""
        outs = run_two_process(_TELEMETRY_2PROC_CHILD, tmp_path,
                               expect="TELEMETRY OK")
        for out in outs:
            assert "[telemetry]" in out, \
                "periodic reporter emitted nothing:\n" + out[-800:]
