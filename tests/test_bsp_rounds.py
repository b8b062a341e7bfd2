"""BSP (``-sync=true``, ``SyncServer``) over a MatrixTable, held to the
plain round-by-round reference (``multiverso_tpu/tables/bsp_reference.py``,
the benchmark's ``reference/bsp_rounds.py`` byte for byte).

Counterpart of reference Test/unittests/test_sync.cpp:25-43 and
Test/test_array_table.cpp:13-47 on the table and the verbs of
Test/test_matrix_perf.cpp: N workers each Add then Get, every Get equals
the round's total. ``tests/test_sync.py`` holds the same guarantee on an
ArrayTable with a closed form; here every Get of every round is compared
bit for bit (whole-number deltas), the asynchronous server is shown to
fail the same check, and the ``server.bsp.*`` spans and instruments are
read. Every blocking call has a time limit of its own (``-mv_deadline_s``
bounds a ``Wait``, every ``join`` has a timeout), so a protocol fault
fails a test and does not hang the suite.
"""

import os
import threading
import time

import numpy as np
import pytest

from multiverso_tpu.tables import MatrixTableOption
from multiverso_tpu.tables.bsp_reference import BspRounds
from multiverso_tpu.telemetry import metrics as tmetrics
from multiverso_tpu.telemetry import trace as ttrace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, COLS, WORKERS, ROUNDS, K, SETS = 20_000, 50, 4, 30, 200, 8
#: every Wait of these worlds raises DeadlineExceeded after this long
BOUNDED = "-mv_deadline_s=60"
JOIN_S = 120


def _traffic(seed: int, rows: int = ROWS, cols: int = COLS, sets: int = SETS):
    """-> (shared id sets, a delta a worker a set): whole numbers, so a
    round's float32 sum is exact in any order."""
    rng = np.random.default_rng(seed)
    ids = [rng.choice(rows, K, replace=False).astype(np.int32)
           for _ in range(sets)]
    deltas = [[rng.integers(-1000, 1001, (K, cols)).astype(np.float32)
               for _ in range(sets)] for _ in range(WORKERS)]
    return ids, deltas


def _world(mv, *flags):
    mv.MV_Init([f"-num_workers={WORKERS}", BOUNDED, *flags])


def _run_threads(work):
    """``work(w)`` on a thread a worker; -> after all have ended."""
    errors = []

    def guarded(w):
        try:
            work(w)
        except Exception as exc:   # told by the assert below
            errors.append((w, repr(exc)))

    threads = [threading.Thread(target=guarded, args=(w,), daemon=True)
               for w in range(WORKERS)]
    for t in threads:
        t.start()
    until = time.monotonic() + JOIN_S
    for t in threads:
        t.join(max(0.0, until - time.monotonic()))
    assert not any(t.is_alive() for t in threads), "a worker never returned"
    assert not errors, errors


def _moved(before: dict, after: dict, name: str) -> float:
    return (after[name].get("value", 0.0)
            - before.get(name, {}).get("value", 0.0))


def _check_rounds(ids, deltas, got, rounds=ROUNDS):
    """Every Get equals the reference's; the Gets of a round are equal."""
    ref = BspRounds(COLS, WORKERS, np.concatenate(ids))
    for r in range(rounds):
        j = r % len(ids)
        ref.round(r, ids[j], [deltas[w][j] for w in range(WORKERS)])
        want = ref.expect_get(r, ids[j])
        for w in range(WORKERS):
            assert np.array_equal(got[w][r], want), (w, r)
            assert np.array_equal(got[w][r], got[0][r]), (w, r)
    return ref


def test_the_two_reference_files_are_one_text():
    with open(os.path.join(REPO, "multiverso_tpu", "tables",
                           "bsp_reference.py"), "rb") as f:
        mine = f.read()
    with open(os.path.join(REPO, "benchmark", "reference",
                           "bsp_rounds.py"), "rb") as f:
        assert f.read() == mine


def test_the_reference_by_hand():
    """Two workers, three named rows, two rounds written out."""
    ref = BspRounds(2, 2, [7, 3, 7, 9])
    assert ref.ids.tolist() == [3, 7, 9]
    ref.round(0, [3, 9], [np.array([[1, 1], [2, 2]]),
                          np.array([[10, 10], [20, 20]])])
    assert ref.expect_get(0, [9, 3, 7]).tolist() == [[22, 22], [11, 11],
                                                     [0, 0]]
    # repeated ids sum
    ref.round(1, [7, 7], [np.array([[1, 0], [1, 0]]),
                          np.array([[0, 5], [0, 5]])])
    assert ref.expect_get(1, [7]).tolist() == [[2, 10]]
    assert ref.table_rows([3]).dtype == np.float32
    with pytest.raises(ValueError):
        ref.expect_get(0, [7])          # round 1 has been applied
    with pytest.raises(ValueError):
        ref.round(3, [3], [np.zeros((1, 2))] * 2)       # out of order
    with pytest.raises(ValueError):
        ref.round(2, [4], [np.zeros((1, 2))] * 2)       # never named
    with pytest.raises(ValueError):
        ref.round(2, [3], [np.zeros((1, 2))])           # a worker short
    # what the asynchronous server may answer worker 0's Get of round 0:
    # its own Add and any count of worker 1's, in order
    assert ref.async_counts(0, 0, [3, 9], [[1, 1], [2, 2]]) == [1, 0]
    assert ref.async_counts(0, 0, [3, 9], [[11, 11], [22, 22]]) == [1, 1]
    # ... not a state without its own Add, nor one no prefix gives
    assert ref.async_counts(0, 0, [3, 9], [[10, 10], [20, 20]]) is None
    assert ref.async_counts(1, 0, [3, 9], [[10, 10], [20, 20]]) == [0, 1]
    assert ref.async_counts(0, 0, [3, 9], [[11, 11], [2, 2]]) is None


@pytest.mark.parametrize("pace", ["lock_step", "staggered"])
def test_every_get_is_its_rounds_total(pace):
    """4 worker threads, 30 rounds on shared id sets: every Get equals
    the reference bit for bit and the four Gets of a round are equal.
    ``staggered``: seeded pauses before each verb push the workers out of
    phase, so Gets and Adds arrive early and wait in the caches."""
    import multiverso_tpu as mv
    ids, deltas = _traffic(50)
    _world(mv, "-sync=true")
    try:
        table = mv.MV_CreateTable(MatrixTableOption(num_rows=ROWS,
                                                    num_cols=COLS))
        got = [[] for _ in range(WORKERS)]

        def work(w):
            pause = np.random.default_rng(w).random((ROUNDS, 2)) * 4e-3
            with mv.MV_WorkerContext(w):
                for r in range(ROUNDS):
                    j = r % SETS
                    if pace == "staggered":
                        time.sleep(pause[r, 0])
                    table.AddRows(ids[j], deltas[w][j])
                    if pace == "staggered":
                        time.sleep(pause[r, 1])
                    got[w].append(table.GetRows(ids[j]).copy())

        _run_threads(work)
        ref = _check_rounds(ids, deltas, got)
        # the table itself, read by all workers in one last round
        last = [None] * WORKERS
        sample = ref.ids[::7]

        def read(w):
            with mv.MV_WorkerContext(w):
                last[w] = table.GetRows(sample).copy()

        _run_threads(read)
        for w in range(WORKERS):
            assert np.array_equal(last[w], ref.table_rows(sample)), w
    finally:
        mv.MV_ShutDown()


def test_the_asynchronous_server_fails_the_same_check():
    """The same ids and deltas without ``-sync``: worker after worker
    runs its round alone (legal there: nothing makes a worker wait; under
    BSP the first Get would wait for the others' Adds), so a Get holds
    the Adds of the workers before it and not of those after it. The
    reference says which Gets BSP forbids and that the asynchronous
    server may give them: the check above cannot pass by accident."""
    import multiverso_tpu as mv
    ids, deltas = _traffic(50)
    rounds = 6
    _world(mv)
    try:
        table = mv.MV_CreateTable(MatrixTableOption(num_rows=ROWS,
                                                    num_cols=COLS))
        got = [[] for _ in range(WORKERS)]
        for r in range(rounds):
            for w in range(WORKERS):
                with mv.MV_WorkerContext(w):
                    table.AddRows(ids[r], deltas[w][r])
                    got[w].append(table.GetRows(ids[r]).copy())
    finally:
        mv.MV_ShutDown()
    with pytest.raises(AssertionError):
        _check_rounds(ids, deltas, got, rounds)
    ref = BspRounds(COLS, WORKERS, np.concatenate(ids))
    for r in range(rounds):
        ref.round(r, ids[r], [deltas[w][r] for w in range(WORKERS)])
    forbidden = 0
    for r in range(rounds):
        for w in range(WORKERS):
            counts = ref.async_counts(w, r, ids[r], got[w][r])
            # workers 0..w have added r + 1 times, the others r times
            assert counts == [r + 1] * (w + 1) + [r] * (WORKERS - w - 1)
            forbidden += counts != [r + 1] * WORKERS
    assert forbidden == rounds * (WORKERS - 1)


def test_two_tables_under_one_set_of_clocks():
    """The clocks count every Get and Add of every table: a round is an
    Add and a Get of each table, and each table's Gets are its own
    rounds' totals."""
    import multiverso_tpu as mv
    wide, rounds = 8, 10
    ids, deltas = _traffic(51)
    ids_b, deltas_b = _traffic(52, rows=3_000, cols=wide)
    _world(mv, "-sync=true")
    try:
        a = mv.MV_CreateTable(MatrixTableOption(num_rows=ROWS,
                                                num_cols=COLS))
        b = mv.MV_CreateTable(MatrixTableOption(num_rows=3_000,
                                                num_cols=wide))
        got_a = [[] for _ in range(WORKERS)]
        got_b = [[] for _ in range(WORKERS)]

        def work(w):
            with mv.MV_WorkerContext(w):
                for r in range(rounds):
                    j = r % SETS
                    a.AddRows(ids[j], deltas[w][j])
                    got_a[w].append(a.GetRows(ids[j]).copy())
                    b.AddRows(ids_b[j], deltas_b[w][j])
                    got_b[w].append(b.GetRows(ids_b[j]).copy())

        _run_threads(work)
    finally:
        mv.MV_ShutDown()
    _check_rounds(ids, deltas, got_a, rounds)
    ref = BspRounds(wide, WORKERS, np.concatenate(ids_b))
    for r in range(rounds):
        j = r % SETS
        ref.round(r, ids_b[j], [deltas_b[w][j] for w in range(WORKERS)])
        for w in range(WORKERS):
            assert np.array_equal(got_b[w][r], ref.expect_get(r, ids_b[j]))


def test_shutdown_drains_a_worker_one_add_ahead():
    """Worker 0 ends a round ahead of the others: its Add is applied at
    once (its Get clock is level), its Get waits in the cache for Adds
    that never come, and ``MV_ShutDown`` (FinishTrain) serves it: the Get
    returns the table with the extra Add and nothing hangs."""
    import multiverso_tpu as mv
    ids, deltas = _traffic(53)
    rounds = 3
    _world(mv, "-sync=true")
    stopped = False
    try:
        table = mv.MV_CreateTable(MatrixTableOption(num_rows=ROWS,
                                                    num_cols=COLS))
        got = [[] for _ in range(WORKERS)]
        ahead = {}

        def work(w):
            with mv.MV_WorkerContext(w):
                for r in range(rounds):
                    table.AddRows(ids[r], deltas[w][r])
                    got[w].append(table.GetRows(ids[r]).copy())
                if w == 0:
                    table.AddRows(ids[rounds], deltas[0][rounds])
                    ahead["get"] = table.GetAsyncHandle(ids[rounds])

        _run_threads(work)
        ref = _check_rounds(ids, deltas, got, rounds)
        before = tmetrics.snapshot()
        assert before["server.bsp.staleness"]["value"] == 1.0
        waiter = threading.Thread(
            target=lambda: ahead.update(rows=table.Wait(ahead["get"])),
            daemon=True)
        waiter.start()
        done = threading.Thread(target=mv.MV_ShutDown, daemon=True)
        done.start()
        done.join(JOIN_S)
        stopped = not done.is_alive()
        assert stopped, "MV_ShutDown never returned"
        waiter.join(JOIN_S)
        assert not waiter.is_alive(), "the Get ahead was never answered"
        want = ref.table_rows(ids[rounds]) + deltas[0][rounds]
        assert np.array_equal(ahead["rows"], want)
    finally:
        if not stopped:
            mv.MV_ShutDown()


def test_the_counters_after_rounds_of_workers():
    import multiverso_tpu as mv
    ids, deltas = _traffic(54)
    rounds = 12
    _world(mv, "-sync=true")
    try:
        table = mv.MV_CreateTable(MatrixTableOption(num_rows=ROWS,
                                                    num_cols=COLS))
        before = tmetrics.snapshot()

        def work(w):
            with mv.MV_WorkerContext(w):
                for r in range(rounds):
                    j = r % SETS
                    table.AddRows(ids[j], deltas[w][j])
                    table.GetRows(ids[j])

        _run_threads(work)
        after = tmetrics.snapshot()
    finally:
        mv.MV_ShutDown()
    assert _moved(before, after, "server.bsp.rounds") == rounds
    assert _moved(before, after, "server.bsp.adds") == WORKERS * rounds
    assert _moved(before, after, "server.bsp.gets") == WORKERS * rounds
    # the worker whose Add ends a round is never cached at its Get
    assert 0 <= _moved(before, after, "server.bsp.gets_cached") \
        <= (WORKERS - 1) * rounds
    # ... and the one whose Get ends a get round never at its next Add
    assert 0 <= _moved(before, after, "server.bsp.adds_cached") \
        <= (WORKERS - 1) * (rounds - 1)
    took = after["server.bsp.round_s"]
    was = before.get("server.bsp.round_s", {"count": 0, "sum": 0.0})
    assert took["count"] - was["count"] == rounds
    assert took["sum"] - was["sum"] > 0
    assert after["server.bsp.staleness"]["value"] == 0.0


def test_the_spans_of_a_held_get_and_of_both_drains():
    """One round staged verb by verb from this thread, with ``-trace``:
    three Gets arrive before the round's last Add and are held; that Add
    sets off the drain that serves them; worker 0's next Add arrives
    before the last Get and is held until that Get ends the get round."""
    import multiverso_tpu as mv
    ids, deltas = _traffic(55)
    _world(mv, "-sync=true", "-trace=true")
    try:
        table = mv.MV_CreateTable(MatrixTableOption(num_rows=ROWS,
                                                    num_cols=COLS))
        ttrace.clear()
        before = tmetrics.snapshot()
        gets = []
        for w in range(WORKERS - 1):
            with mv.MV_WorkerContext(w):
                table.AddRows(ids[0], deltas[w][0])
                gets.append(table.GetAsyncHandle(ids[0]))
        last = WORKERS - 1
        with mv.MV_WorkerContext(last):
            table.AddRows(ids[0], deltas[last][0])    # drains the three
        rows = [table.Wait(h).copy() for h in gets]
        with mv.MV_WorkerContext(0):
            early = table.AddAsyncHandle(deltas[0][1], ids[1])   # held
        with mv.MV_WorkerContext(last):
            rows.append(table.GetRows(ids[0]).copy())  # ends the get round
        table.Wait(early)
        after = tmetrics.snapshot()
        spans = [e for e in ttrace.to_chrome_trace()["traceEvents"]
                 if e.get("ph") == "X"]
    finally:
        mv.MV_ShutDown()
    ref = BspRounds(COLS, WORKERS, np.concatenate(ids))
    ref.round(0, ids[0], [deltas[w][0] for w in range(WORKERS)])
    for got in rows:
        assert np.array_equal(got, ref.expect_get(0, ids[0]))
    assert _moved(before, after, "server.bsp.gets_cached") == WORKERS - 1
    assert _moved(before, after, "server.bsp.adds_cached") == 1
    assert _moved(before, after, "server.bsp.rounds") == 1
    events = [e for e in spans if e["name"].startswith("server.bsp.")]
    holds = [e for e in events if e["name"] == "server.bsp.get_hold"]
    drains = sorted((e for e in events if e["name"] == "server.bsp.drain"),
                    key=lambda e: e["ts"])
    assert len(holds) == WORKERS - 1 and len(drains) == 2
    assert {e["cat"] for e in events} == {"server"}
    first = drains[0]
    for hold in holds:
        # a hold ends where its Get's service starts: inside the drain
        end = hold["ts"] + hold["dur"]
        assert first["ts"] <= end <= first["ts"] + first["dur"]
        assert hold["ts"] < first["ts"]
    # the drain's children are the table's blocking Gets
    served = [e["name"] for e in spans
              if e["args"]["parent_id"] == first["args"]["span_id"]]
    assert served == ["server.table.get"] * (WORKERS - 1)


class TestHeldSpan:
    """``telemetry.trace.begin``: a span whose two ends are not one
    ``with`` block."""

    def test_off_is_the_shared_no_op(self):
        ttrace._reset_for_tests()
        held = ttrace.begin("server.x.hold", cat="server")
        assert held is ttrace.NULL_SPAN
        held.end()
        assert not [e for e in ttrace.to_chrome_trace()["traceEvents"]
                    if e.get("ph") == "X"]

    def test_on_records_one_event_outside_the_nesting(self):
        from multiverso_tpu.utils.configure import SetCMDFlag
        ttrace._reset_for_tests()
        SetCMDFlag("trace", True)
        try:
            first = ttrace.begin("server.x.hold", cat="server")
            second = ttrace.begin("server.x.hold", cat="server")
            with ttrace.span("server.x.work", cat="server") as ctx:
                # a held span is nobody's parent
                assert ttrace.current_ctx() == ctx
                first.end()             # ends in any order, inside others
            assert ttrace.current_ctx() is None
            second.end()
        finally:
            SetCMDFlag("trace", False)
        events = [e for e in ttrace.to_chrome_trace()["traceEvents"]
                  if e.get("ph") == "X"]
        assert [e["name"] for e in events] == [
            "server.x.hold", "server.x.work", "server.x.hold"]
        assert all(e["args"]["parent_id"] == 0 for e in events)
        assert events[0]["ts"] <= events[2]["ts"]
        assert all(e["dur"] >= 0 for e in events)
        ttrace._reset_for_tests()
