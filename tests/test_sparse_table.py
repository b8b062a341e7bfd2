"""SparseMatrixTable against its plain reference
(``multiverso_tpu/tables/sparse_reference.py``: a float32 matrix, a
``(workers, rows)`` bool matrix, the reference's loops).

Every case drives the table through the normal path (``MV_Init`` ->
``MV_CreateTable`` -> worker verbs -> engine -> table) on seeded random
interleavings of Adds and Gets and holds it to the reference verb by verb:
no Get may return a row that is fresh for its worker, skip one that is
stale, or return one twice, and the rows returned are the table's, bit for
bit (whole-number deltas). Each case runs under both linear updaters:
``default`` adds a delta, ``sgd`` subtracts it, and so does the reference.
"""

import threading

import numpy as np
import pytest

from multiverso_tpu.tables import SparseMatrixTableOption
from multiverso_tpu.tables.sparse_matrix_table import (
    SparseMatrixServerTable, _DirtyRows)
from multiverso_tpu.tables.sparse_reference import SparseReference
from multiverso_tpu.telemetry import metrics
from multiverso_tpu.updaters.base import AddOption, GetOption

ROWS, COLS = 96, 5


def _world(workers: int):
    import multiverso_tpu as mv
    mv.MV_Init([f"-num_workers={workers}"])
    return mv


def _deltas(rng, n: int, sparse: bool) -> np.ndarray:
    d = rng.integers(-3, 4, (n, COLS)).astype(np.float32)
    if sparse:      # mostly zeros: the SparseFilter then compresses it
        d[rng.random((n, COLS)) < 0.8] = 0.0
    return d


def _assert_get(got, want, ref, what):
    ids, rows = got
    want_ids = np.sort(want[0])
    assert ids.dtype == np.int32 and ids.tolist() == want_ids.tolist(), what
    np.testing.assert_array_equal(rows, ref.data[ids], err_msg=what)


def _interleave(table, ref, rng, workers: int, steps: int, sparse: bool,
                sign: float):
    """``steps`` random verbs, each checked against the reference, which
    adds ``sign`` times what the table is handed."""
    for step in range(steps):
        kind = rng.choice(["add", "add", "get", "get", "get_rows",
                           "add_all", "add_nobody", "get_everything"],
                          p=[.25, .2, .2, .1, .15, .03, .04, .03])
        w = int(rng.integers(0, workers))
        what = f"step {step}: {kind} by worker {w}"
        if kind in ("add", "add_nobody"):
            n = int(rng.integers(1, 24))
            ids = rng.integers(0, ROWS, n).astype(np.int32)  # may repeat
            d = _deltas(rng, n, sparse)
            if kind == "add_nobody":
                w = -1          # no keeper: stale for every worker
            table.AddRows(ids, d, AddOption(worker_id=w))
            ref.add(w, ids, sign * d)
        elif kind == "add_all":
            d = _deltas(rng, ROWS, False)
            table.Add(d, AddOption(worker_id=w))
            ref.add(w, None, sign * d)
        elif kind == "get":
            _assert_get(table.Get(GetOption(worker_id=w)), ref.get(w),
                        ref, what)
        elif kind == "get_rows":
            ask = rng.integers(0, ROWS, int(rng.integers(1, 40))).astype(
                np.int32)     # may repeat: each stale row comes back once
            _assert_get(table.GetRows(ask, GetOption(worker_id=w)),
                        ref.get(w, ask), ref, what)
        else:
            before = table.server().up_to_date
            _assert_get(table.Get(GetOption(worker_id=-1)), ref.get(-1),
                        ref, what)
            np.testing.assert_array_equal(table.server().up_to_date, before)
        np.testing.assert_array_equal(table.server().up_to_date,
                                      ref.up_to_date, err_msg=what)


@pytest.mark.parametrize("compress", [None, "sparse"])
@pytest.mark.parametrize("updater_type", ["default", "sgd"])
@pytest.mark.parametrize("workers", [1, 2, 3, 4])
def test_interleavings_match_reference(workers, updater_type, compress):
    mv = _world(workers)
    try:
        table = mv.MV_CreateTable(SparseMatrixTableOption(
            num_rows=ROWS, num_cols=COLS, compress=compress,
            updater_type=updater_type))
        ref = SparseReference(ROWS, COLS, workers)
        rng = np.random.default_rng(1000 * workers + (updater_type == "sgd")
                                    + 2 * (compress is not None))
        _interleave(table, ref, rng, workers, steps=120,
                    sparse=compress is not None,
                    sign=-1.0 if updater_type == "sgd" else 1.0)
        # and at the end every worker drains to the same table
        for w in range(workers):
            _assert_get(table.Get(GetOption(worker_id=w)), ref.get(w), ref,
                        f"last get of worker {w}")
            assert table.Get(GetOption(worker_id=w))[0].tolist() == [0]
    finally:
        mv.MV_ShutDown()


@pytest.mark.parametrize("updater_type", ["default", "sgd"])
def test_threads_under_worker_context(updater_type):
    """Four worker threads in rounds of Get-all / AddRows / Get-all (the
    cell ``mt_sparse_rounds`` in small). Threads interleave, so the check
    is the order-free one: what a worker's Gets returned is covered by
    the other workers' Adds, nothing is returned twice without an Add
    between (each id at most as often as others added it), and after the
    threads end one more Get a worker drains the rest."""
    workers, rounds, k = 4, 12, 10
    mv = _world(workers)
    try:
        table = mv.MV_CreateTable(SparseMatrixTableOption(
            num_rows=ROWS, num_cols=COLS, updater_type=updater_type))
        rng = np.random.default_rng(7)
        # row 0 is never added, so a Get that answers [0] found nothing
        ids = [[1 + rng.choice(ROWS - 1, k, replace=False).astype(np.int32)
                for _ in range(rounds)] for _ in range(workers)]
        got = [[] for _ in range(workers)]
        errors = []

        def work(w):
            try:
                with mv.MV_WorkerContext(w):
                    for r in range(rounds):
                        got[w].append(table.Get()[0])
                        table.AddRows(ids[w][r],
                                      np.ones((k, COLS), np.float32))
                        got[w].append(table.Get()[0])
            except Exception as exc:     # pragma: no cover
                errors.append(exc)

        threads = [threading.Thread(target=work, args=(w,))
                   for w in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errors, errors
        replay = np.zeros((ROWS, COLS), np.float32)
        for w in range(workers):
            for a in ids[w]:
                replay[a] += -1.0 if updater_type == "sgd" else 1.0
        for w in range(workers):
            with mv.MV_WorkerContext(w):
                last_ids, last_rows = table.Get()
                np.testing.assert_array_equal(last_rows, replay[last_ids])
                assert table.Get()[0].tolist() == [0]
            others = np.bincount(np.concatenate(
                [a for v in range(workers) if v != w for a in ids[v]]),
                minlength=ROWS)
            seen = np.bincount(np.concatenate(
                [g for g in got[w] + [last_ids] if g.tolist() != [0]]),
                minlength=ROWS)
            assert np.all(seen <= others), (w, np.nonzero(seen > others))
            assert np.all(seen[others > 0] >= 1), w
    finally:
        mv.MV_ShutDown()


def _touched_at(num_rows: int) -> int:
    """The same traffic on a table of ``num_rows`` rows: ids under 64."""
    import multiverso_tpu as mv
    mv.MV_Init(["-num_workers=3"])
    try:
        table = mv.MV_CreateTable(SparseMatrixTableOption(
            num_rows=num_rows, num_cols=2))
        rng = np.random.default_rng(3)
        for _ in range(30):
            w = int(rng.integers(0, 3))
            ids = rng.integers(0, 64, 9).astype(np.int32)
            table.AddRows(ids, np.ones((9, 2), np.float32),
                          AddOption(worker_id=w))
            table.Get(GetOption(worker_id=(w + 1) % 3))
            table.GetRows(ids[:4], GetOption(worker_id=(w + 2) % 3))
        return table.server().select_touched
    finally:
        mv.MV_ShutDown()


def test_select_work_follows_rows_marked_not_table_rows():
    """What the issue asks in place of a timing: the number of elements
    the selects read or write is a function of the rows marked. The same
    verbs on a table 500 times as long touch exactly as many."""
    small, large = _touched_at(200), _touched_at(100_000)
    assert small == large and 0 < small < 30 * 3 * 64


def test_dirty_rows_set_algebra():
    d = _DirtyRows()
    assert d.drain().tolist() == [] and d.take_stale(np.array([1], np.int32)
                                               ).tolist() == []
    d.mark(np.array([5, 3, 5], np.int32), 100)
    d.mark(np.array([9, 3], np.int32), 100)
    assert d.take_stale(np.array([3, 3, 4, 9, 77], np.int32)).tolist() == [3, 9]
    assert d.take_stale(np.array([3], np.int32)).tolist() == []
    d.mark(np.array([3], np.int32), 100)
    assert d.drain().tolist() == [3, 5] and d.drain().tolist() == []
    # past the limit the chunks fold: the set never outgrows the table
    for _ in range(10):
        d.mark(np.arange(8, dtype=np.int32), 16)
    assert d.pending <= 16 and d.drain().tolist() == list(range(8))
    d.mark_all(np.arange(6, dtype=np.int32))
    assert d.take_stale(np.array([2, 4], np.int32)).tolist() == [2, 4]
    assert d.drain().tolist() == [0, 1, 3, 5]


def test_an_adds_id_array_is_copied():
    """The caller may write its id array again after a blocking Add."""
    mv = _world(2)
    try:
        table = mv.MV_CreateTable(SparseMatrixTableOption(
            num_rows=ROWS, num_cols=COLS))
        ids = np.array([4, 8], np.int32)
        table.AddRows(ids, np.ones((2, COLS), np.float32),
                      AddOption(worker_id=0))
        ids[:] = 90
        assert table.Get(GetOption(worker_id=1))[0].tolist() == [4, 8]
    finally:
        mv.MV_ShutDown()


def test_read_rows_in_pieces_and_buckets(monkeypatch):
    """``read_rows`` hands the gather only ladder rungs up to the cap and
    reads a larger set in pieces; worker -1 (the whole table) takes that
    path at a small size here and stays out of the chip run."""
    rungs = SparseMatrixServerTable.read_buckets()
    assert rungs[0] == 8 and rungs[-1] == SparseMatrixServerTable.READ_ROWS_CAP
    assert list(rungs) == sorted(set(rungs))
    monkeypatch.setattr(SparseMatrixServerTable, "READ_ROWS_CAP", 16)
    assert SparseMatrixServerTable.read_buckets() == (8, 16)
    mv = _world(2)
    try:
        table = mv.MV_CreateTable(SparseMatrixTableOption(
            num_rows=ROWS, num_cols=COLS))
        srv = table.server()
        full = np.arange(ROWS * COLS, dtype=np.float32).reshape(ROWS, COLS)
        table.Add(full, AddOption(worker_id=0))
        shapes = []
        gather = srv._gather_rows
        monkeypatch.setattr(srv, "_gather_rows", lambda d, a, ids: (
            shapes.append(ids.shape[0]), gather(d, a, ids))[1])
        ids, rows = table.Get(GetOption(worker_id=-1))
        assert ids.tolist() == list(range(ROWS))
        np.testing.assert_array_equal(rows, full)
        assert set(shapes) <= {8, 16} and len(shapes) == ROWS // 16
        del shapes[:]
        ask = np.array([70, 3, 41], np.int32)
        np.testing.assert_array_equal(srv.read_rows(ask), full[ask])
        assert shapes == [8]
    finally:
        mv.MV_ShutDown()


def test_spans_and_counters():
    """The names PERF.md's tables and the cell's readers rely on."""
    import multiverso_tpu as mv
    from multiverso_tpu.telemetry import trace as ttrace
    mv.MV_Init(["-num_workers=3", "-trace=true"])
    try:
        table = mv.MV_CreateTable(SparseMatrixTableOption(
            num_rows=ROWS, num_cols=COLS))

        def moved(name, before):
            return (metrics.snapshot().get(name, {}).get("value", 0)
                    - before.get(name, {}).get("value", 0))

        before = metrics.snapshot()
        table.AddRows([2, 4, 4], np.ones((3, COLS), np.float32),
                      AddOption(worker_id=0))
        assert moved("table.sparse.add.marked", before) == 3 * 2
        assert table.Get(GetOption(worker_id=1))[0].tolist() == [2, 4]
        assert moved("table.sparse.get.rows", before) == 2
        assert moved("table.sparse.get.empty", before) == 0
        table.Get(GetOption(worker_id=1))
        table.Get(GetOption(worker_id=0))
        assert moved("table.sparse.get.empty", before) == 2
        assert moved("table.sparse.get.rows", before) == 2
        names = {e["name"] for e in ttrace.to_chrome_trace()["traceEvents"]}
        assert {"server.table.sparse.get.select",
                "server.table.sparse.get.read",
                "server.table.sparse.add.mark"} <= names
    finally:
        mv.MV_ShutDown()


_TWO_PROC_CHILD = r'''
import os, sys
rank, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.parallel import multihost
from multiverso_tpu.tables import SparseMatrixTableOption
from multiverso_tpu.tables.sparse_reference import SparseReference
from multiverso_tpu.updaters.base import AddOption, GetOption

W = 2
mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2", f"-num_workers={W}"])
R, C = 48, 3
t = mv.MV_CreateTable(SparseMatrixTableOption(num_rows=R, num_cols=C))
# the contract: dirty sets replicated, keyed by GLOBAL worker id
# rank * W + w, transitions applied in rank order. Both ranks draw both
# ranks' verbs from one seed and replay the global stream in the plain
# reference with 2 * W workers.
ref = SparseReference(R, C, 2 * W)
rng = np.random.default_rng(11)
for step in range(40):
    w = int(rng.integers(0, W))         # the same local worker everywhere
    kind = rng.choice(["add", "get", "get_rows"])
    if kind == "add":
        parts = [(rng.integers(0, R, 6).astype(np.int32),
                  rng.integers(-2, 3, (6, C)).astype(np.float32))
                 for _ in range(2)]
        t.AddRows(*parts[rank], AddOption(worker_id=w))
        for r, (ids, d) in enumerate(parts):
            ref.add(r * W + w, ids, d)
    else:
        asks = [None if kind == "get"
                else rng.integers(0, R, 9).astype(np.int32)
                for _ in range(2)]
        opt = GetOption(worker_id=w)
        ids, rows = (t.Get(opt) if kind == "get"
                     else t.GetRows(asks[rank], opt))
        want = [ref.get(r * W + w, asks[r]) for r in range(2)][rank]
        assert ids.tolist() == np.sort(want[0]).tolist(), (step, kind, ids)
        assert np.array_equal(rows, ref.data[ids]), (step, kind)
    assert np.array_equal(t.server().up_to_date, ref.up_to_date), step
# one worker thread a process runs here: MV_Barrier would wait for both
multihost.host_barrier()
mv.MV_ShutDown()
print(f"child {rank} SPARSEREF OK", flush=True)
'''


def test_two_processes_match_reference(tmp_path):
    from tests.test_multihost import run_two_process
    run_two_process(_TWO_PROC_CHILD, tmp_path, expect="SPARSEREF OK")
