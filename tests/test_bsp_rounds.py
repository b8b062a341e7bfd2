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

The BSP engine serves what its mailbox holds as one window (PR 51). The
window tests stage the mailbox from this thread with ``AddAsyncHandle``
/ ``GetAsyncHandle`` while the engine's thread is held inside a message
of its own (``_engine_held``), so a window's content is exact and no
case depends on a race or reads the clock.

A stretch of two or more Adds whose payloads name the same rows is summed
on the host and applied as ONE lone Add (PR 53, ``ProcessAddSameRows``):
one dispatch, one merged run; any other stretch goes verb by verb.
"""

import contextlib
import os
import threading
import time

import jax
import numpy as np
import pytest

from multiverso_tpu.message import Message, MsgType
from multiverso_tpu.tables import (MatrixTableOption,
                                   SparseMatrixTableOption)
from multiverso_tpu.tables.bsp_reference import BspRounds
from multiverso_tpu.telemetry import metrics as tmetrics
from multiverso_tpu.telemetry import trace as ttrace
from multiverso_tpu.utils.waiter import Waiter

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
    """What a counter gained (0 for one that nothing has registered)."""
    return (after.get(name, {}).get("value", 0.0)
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
    # every Add is a lone dispatch or one of a same-rows run, whatever
    # stretches the workers' sends made
    runs, in_runs = (_moved(before, after, name)
                     for name in _SUMMED_COUNTERS)
    dispatches = _moved(before, after, "server.add.dispatches")
    assert _moved(before, after, "server.add.run_merged") == runs
    assert dispatches - runs + in_runs == WORKERS * rounds
    assert 2 * runs <= in_runs <= WORKERS * rounds


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
    # the drain dispatches its three Gets as one stretch among the
    # window's own: they name the same rows, so one gather is dispatched
    # (and copied back and answered when the window finalizes)
    served = [e["name"] for e in spans
              if e["args"]["parent_id"] == first["args"]["span_id"]]
    assert served == ["server.table.get.prepare", "server.table.get.dispatch"]
    assert _moved(before, after, "server.get.shared") == WORKERS - 2


# -- the window of the BSP engine (PR 51) -------------------------------------

#: every program JAX traced and every one it compiled, in order
_COMPILES = []
jax.monitoring.register_event_duration_secs_listener(
    lambda name, secs, **kw: _COMPILES.append(name)
    if name in ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/backend_compile_duration") else None)


def _engine_message(msg_type, **fields) -> Message:
    """A message of this thread's own, sent; its ``waiter`` tells the
    reply."""
    from multiverso_tpu.zoo import Zoo
    msg = Message(msg_type=msg_type, waiter=Waiter(1), **fields)
    Zoo.Get().SendToServer(msg)
    return msg


def _answer(msg: Message):
    assert msg.waiter.Wait(JOIN_S), "the engine never answered"
    assert not isinstance(msg.result, Exception), msg.result
    return msg.result


@contextlib.contextmanager
def _engine_held():
    """The engine's thread waits inside a StoreLoad payload of ours, so
    all that is sent meanwhile is in its mailbox when it goes on: the
    next window is exactly that, in that order (16 messages at most,
    ``GET_PIPELINE_WINDOW``)."""
    inside, go_on = threading.Event(), threading.Event()

    def hold():
        inside.set()
        assert go_on.wait(JOIN_S), "the test never let the engine go on"

    held = _engine_message(MsgType.Request_StoreLoad, payload={"fn": hold})
    assert inside.wait(JOIN_S), "the engine never reached the hold"
    try:
        yield
    finally:
        go_on.set()
    _answer(held)


def _settled_snapshot() -> dict:
    """The instruments once the engine has ended the window it is in:
    a window counts itself when its last reply has been sent, and the
    ping is answered behind that."""
    _answer(_engine_message(MsgType.Request_Barrier))
    return tmetrics.snapshot()


class _Staged:
    """Verbs sent from this thread under a worker's context; ``rows()``
    waits for them all and gives the Gets' answers in the order sent."""

    def __init__(self, mv):
        self.mv, self.adds, self.gets, self.answers = mv, [], [], []

    def add(self, table, w, ids, delta):
        with self.mv.MV_WorkerContext(w):
            self.adds.append((table, table.AddAsyncHandle(delta, ids)))

    def get(self, table, w, ids=None):
        with self.mv.MV_WorkerContext(w):
            self.gets.append((table, table.GetAsyncHandle(ids)))

    def rows(self) -> list:
        for table, handle in self.adds:
            table.Wait(handle)
        for table, handle in self.gets:
            got = table.Wait(handle)
            self.answers.append(tuple(np.array(part) for part in got)
                                if isinstance(got, tuple) else np.array(got))
        self.adds, self.gets = [], []
        return self.answers


_WINDOW_COUNTERS = ("server.add.run_merged", "server.add.dispatches",
                    "server.get.shared", "server.bsp.rounds",
                    "server.bsp.gets_cached", "server.bsp.adds_cached",
                    "server.window.verbs", "server.window.barrier_splits")
#: the table's own count of its same-rows runs and of the Adds in them
#: (registered by the first stretch of two or more a table is offered)
_SUMMED_COUNTERS = ("table.add_run.summed", "table.add_run.summed_adds")


def _window_moved(before: dict, after: dict) -> dict:
    """What one staged window moved, and that it WAS one window."""
    moved = {name.split(".", 1)[1]: _moved(before, after, name)
             for name in _WINDOW_COUNTERS + _SUMMED_COUNTERS}
    moved["windows"] = (after["server.window.latency_s"]["count"]
                        - before.get("server.window.latency_s",
                                     {"count": 0})["count"])
    return moved


def _total(deltas, j, workers=range(WORKERS)):
    return sum(deltas[w][j] for w in workers)


def test_a_whole_round_queued_is_one_window_and_one_gather():
    """(a) Four Adds then four Gets in the mailbox are ONE window: the
    Adds one stretch that names one id set, summed on the host and
    applied as ONE lone Add (one dispatch, one merged run, four Adds
    summed), the Gets one gather that three of them share, and every Get
    is the round's total."""
    import multiverso_tpu as mv
    ids, deltas = _traffic(60)
    _world(mv, "-sync=true")
    try:
        table = mv.MV_CreateTable(MatrixTableOption(num_rows=ROWS,
                                                    num_cols=COLS))
        sent = _Staged(mv)
        before = _settled_snapshot()
        with _engine_held():
            for w in range(WORKERS):
                sent.add(table, w, ids[0], deltas[w][0])
            for w in range(WORKERS):
                sent.get(table, w, ids[0])
        rows = sent.rows()
        moved = _window_moved(before, _settled_snapshot())
    finally:
        mv.MV_ShutDown()
    ref = BspRounds(COLS, WORKERS, np.concatenate(ids))
    ref.round(0, ids[0], [deltas[w][0] for w in range(WORKERS)])
    for got in rows:
        assert np.array_equal(got, ref.expect_get(0, ids[0]))
    assert moved == {"add.run_merged": 1, "add.dispatches": 1,
                     "add_run.summed": 1, "add_run.summed_adds": WORKERS,
                     "get.shared": WORKERS - 1, "bsp.rounds": 1,
                     "bsp.gets_cached": 0, "bsp.adds_cached": 0,
                     "window.verbs": 2 * WORKERS,
                     "window.barrier_splits": 0, "windows": 1}


@pytest.mark.parametrize("batch", ["gets_then_the_next_add",
                                   "an_add_between_the_gets",
                                   "last_add_gets_then_next_adds"])
def test_no_add_passes_a_get_the_clocks_placed_before_it(batch):
    """(b) The ordering rule. Adds of round 1 sit in one batch behind,
    or among, Gets of round 0: the Gets are round 0's total and hold
    none of them. (The asynchronous engine's cut would apply every Add
    of the batch at the first Add's position, ahead of the Gets.) The
    Adds behind the last Get are applied after the Gets' gather was
    dispatched and before its copy back is finalized; the Add AMONG the
    Gets is held by the clocks and applied by the drain that the last
    Get's tick sets off, at the same point: the Gets hold none of
    them."""
    import multiverso_tpu as mv
    ids, deltas = _traffic(61)
    last = WORKERS - 1
    _world(mv, "-sync=true")
    try:
        table = mv.MV_CreateTable(MatrixTableOption(num_rows=ROWS,
                                                    num_cols=COLS))

        def add(w, j):
            with mv.MV_WorkerContext(w):
                table.AddRows(ids[0], deltas[w][j])

        sent = _Staged(mv)
        if batch == "last_add_gets_then_next_adds":
            for w in range(last):
                add(w, 0)
            first = []
            before = _settled_snapshot()
            with _engine_held():
                sent.add(table, last, ids[0], deltas[last][0])
                for w in range(WORKERS):
                    sent.get(table, w, ids[0])
                sent.add(table, 0, ids[0], deltas[0][1])
                sent.add(table, 1, ids[0], deltas[1][1])
            # three stretches: the round's last Add, a lone one; the four
            # Gets; the next round's two Adds, summed
            want = {"add.run_merged": 1, "add.dispatches": 2,
                    "add_run.summed_adds": 2,
                    "get.shared": WORKERS - 1, "bsp.rounds": 1,
                    "bsp.adds_cached": 0, "window.verbs": WORKERS + 3,
                    "windows": 1}
            rest = range(2, WORKERS)
        else:
            for w in range(WORKERS):
                add(w, 0)
            with mv.MV_WorkerContext(0):
                first = [table.GetRows(ids[0]).copy()]
            before = _settled_snapshot()
            among = batch == "an_add_between_the_gets"
            with _engine_held():
                sent.get(table, 1, ids[0])
                if among:
                    sent.add(table, 0, ids[0], deltas[0][1])
                for w in range(2, WORKERS):
                    sent.get(table, w, ids[0])
                if not among:
                    sent.add(table, 0, ids[0], deltas[0][1])
            want = {"add.run_merged": 0, "add.dispatches": 1,
                    "get.shared": WORKERS - 2, "bsp.rounds": 0,
                    "bsp.adds_cached": int(among),
                    "window.verbs": WORKERS, "windows": 1}
            rest = range(1, WORKERS)
        rows = first + sent.rows()
        moved = _window_moved(before, _settled_snapshot())
        # the Adds behind the Gets did land: round 1, ended by hand
        for w in rest:
            add(w, 1)
        then = []
        for w in range(WORKERS):
            with mv.MV_WorkerContext(w):
                then.append(table.GetRows(ids[0]).copy())
    finally:
        mv.MV_ShutDown()
    assert len(rows) == WORKERS
    for got in rows:
        assert np.array_equal(got, _total(deltas, 0))
    for got in then:
        assert np.array_equal(got, _total(deltas, 0) + _total(deltas, 1))
    assert moved["bsp.gets_cached"] == 0
    assert {k: moved[k] for k in want} == want


def test_a_get_queued_before_the_rounds_last_add_is_held_and_holds_it():
    """(c) Worker 0's Get sits in the batch BEFORE worker 3's Add: the
    clocks hold it, that Add's tick drains it, and it holds that Add.
    The Add joins the stretch of the three before it: the clocks placed
    the Get behind it, and the four are summed and applied as one lone
    Add. The drain dispatches the held Get at the tick's position, and
    the three Gets behind it share its gather."""
    import multiverso_tpu as mv
    ids, deltas = _traffic(62)
    last = WORKERS - 1
    _world(mv, "-sync=true", "-trace=true")
    try:
        table = mv.MV_CreateTable(MatrixTableOption(num_rows=ROWS,
                                                    num_cols=COLS))
        sent = _Staged(mv)
        ttrace.clear()
        before = _settled_snapshot()
        with _engine_held():
            for w in range(last):
                sent.add(table, w, ids[0], deltas[w][0])
            sent.get(table, 0, ids[0])
            sent.add(table, last, ids[0], deltas[last][0])
            for w in range(1, WORKERS):
                sent.get(table, w, ids[0])
        rows = sent.rows()
        moved = _window_moved(before, _settled_snapshot())
        spans = [e for e in ttrace.to_chrome_trace()["traceEvents"]
                 if e.get("ph") == "X"]
    finally:
        mv.MV_ShutDown()
    for got in rows:
        assert np.array_equal(got, _total(deltas, 0))
    assert moved == {"add.run_merged": 1, "add.dispatches": 1,
                     "add_run.summed": 1, "add_run.summed_adds": WORKERS,
                     "get.shared": WORKERS - 1, "bsp.rounds": 1,
                     "bsp.gets_cached": 1, "bsp.adds_cached": 0,
                     "window.verbs": 2 * WORKERS,
                     "window.barrier_splits": 0, "windows": 1}
    drains = [e for e in spans if e["name"] == "server.bsp.drain"]
    holds = [e for e in spans if e["name"] == "server.bsp.get_hold"]
    assert len(drains) == 1 and len(holds) == 1
    drain = drains[0]
    assert (drain["ts"] <= holds[0]["ts"] + holds[0]["dur"]
            <= drain["ts"] + drain["dur"])
    # the stretch that holds the last Add ends before the drain starts,
    # and the round's one gather is dispatched inside the drain
    adds = sorted((e for e in spans
                   if e["name"] == "server.table.add_run.dispatch"),
                  key=lambda e: e["ts"])
    assert len(adds) == 1 and adds[0]["args"]["adds"] == WORKERS
    merges = [e for e in spans
              if e["name"] == "server.table.add_run.merge"]
    assert len(merges) == 1 and merges[0]["args"]["adds"] == WORKERS
    assert adds[-1]["ts"] + adds[-1]["dur"] <= drain["ts"]
    dispatched = [e["ts"] for e in spans
                  if e["name"] == "server.table.get.dispatch"]
    assert len(dispatched) == 1
    assert drain["ts"] <= dispatched[0] <= drain["ts"] + drain["dur"]


@pytest.mark.parametrize("barrier", ["finish_train", "store_load"])
def test_a_message_that_is_no_verb_ends_the_stretches(barrier):
    """(d) A FinishTrain, and a StoreLoad, inside a batch: the Adds in
    front of it are applied when it runs (three, and two: each stretch
    summed into one lone Add), the verbs behind it are judged and served
    after it."""
    import multiverso_tpu as mv
    from multiverso_tpu.updaters.base import GetOption
    ids, deltas = _traffic(63)
    last = WORKERS - 1
    _world(mv, "-sync=true")
    try:
        table = mv.MV_CreateTable(MatrixTableOption(num_rows=ROWS,
                                                    num_cols=COLS))
        sent = _Staged(mv)
        before = _settled_snapshot()
        if barrier == "finish_train":
            # worker 3 ends training in the middle of round 0: worker
            # 0's Get, held for worker 3's Add, is drained by it
            with _engine_held():
                for w in range(last):
                    sent.add(table, w, ids[0], deltas[w][0])
                sent.get(table, 0, ids[0])
                ended = _engine_message(MsgType.Server_Finish_Train,
                                        src=last)
                for w in range(1, last):
                    sent.get(table, w, ids[0])
            seen_by_it = None
            want_rows = [_total(deltas, 0, range(last))] * last
            want = {"add.run_merged": 1, "add.dispatches": 1,
                    "add_run.summed_adds": last,
                    "bsp.gets_cached": 1, "get.shared": last - 2,
                    "window.verbs": 2 * last, "window.barrier_splits": 1,
                    "windows": 1}
        else:
            def read():
                return np.array(table.server().ProcessGet(
                    GetOption(), row_ids=ids[0]))

            with _engine_held():
                for w in range(2):
                    sent.add(table, w, ids[0], deltas[w][0])
                ended = _engine_message(MsgType.Request_StoreLoad,
                                        payload={"fn": read})
                for w in range(2, WORKERS):
                    sent.add(table, w, ids[0], deltas[w][0])
                for w in range(WORKERS):
                    sent.get(table, w, ids[0])
            seen_by_it = _total(deltas, 0, range(2))
            want_rows = [_total(deltas, 0)] * WORKERS
            want = {"add.run_merged": 2, "add.dispatches": 2,
                    "add_run.summed_adds": WORKERS,
                    "bsp.gets_cached": 0, "get.shared": WORKERS - 1,
                    "window.verbs": 2 * WORKERS,
                    "window.barrier_splits": 1, "windows": 1}
        rows = sent.rows()
        result = _answer(ended)
        moved = _window_moved(before, _settled_snapshot())
    finally:
        mv.MV_ShutDown()
    if seen_by_it is not None:
        assert np.array_equal(result, seen_by_it)
    assert len(rows) == len(want_rows)
    for got, want_got in zip(rows, want_rows):
        assert np.array_equal(got, want_got)
    assert {k: moved[k] for k in want} == want


def test_two_tables_interleaved_in_one_batch():
    """(e) A verb of another table ends an Add stretch; Gets of both
    tables are one stretch in which each table's share a gather."""
    import multiverso_tpu as mv
    wide = 8
    ids, deltas = _traffic(64)
    ids_b, deltas_b = _traffic(65, rows=3_000, cols=wide)
    _world(mv, "-sync=true")
    try:
        a = mv.MV_CreateTable(MatrixTableOption(num_rows=ROWS,
                                                num_cols=COLS))
        b = mv.MV_CreateTable(MatrixTableOption(num_rows=3_000,
                                                num_cols=wide))
        sent = _Staged(mv)
        before = _settled_snapshot()
        with _engine_held():
            for pair in ((0, 1), (2, 3)):
                for w in pair:
                    sent.add(a, w, ids[0], deltas[w][0])
                for w in pair:
                    sent.add(b, w, ids_b[0], deltas_b[w][0])
            for w in range(WORKERS):
                sent.get(a, w, ids[0])
                sent.get(b, w, ids_b[0])
        rows = sent.rows()
        moved = _window_moved(before, _settled_snapshot())
    finally:
        mv.MV_ShutDown()
    for got_a, got_b in zip(rows[0::2], rows[1::2]):
        assert np.array_equal(got_a, _total(deltas, 0))
        assert np.array_equal(got_b, _total(deltas_b, 0))
    assert moved["windows"] == 1 and moved["window.verbs"] == 4 * WORKERS
    # four stretches of two, each summed, where the asynchronous cut
    # makes two runs of four
    assert (moved["add.run_merged"], moved["add.dispatches"]) == (4, 4)
    assert moved["add_run.summed_adds"] == 2 * WORKERS
    assert moved["get.shared"] == 2 * (WORKERS - 1)


@pytest.mark.parametrize("kind", ["momentum", "adagrad", "sparse"])
def test_the_window_answers_as_one_verb_at_a_time(kind):
    """(f) A table whose updater is not linear (the order of its Adds
    shows in the rows), and a SparseMatrixTable (no two-phase Get,
    answers that depend on who asks): two rounds queued whole give, Get
    for Get, what the same verbs give sent one after another. The
    non-linear tables decline the same-rows run and go verb by verb; the
    SparseMatrixTable's updater is linear, so its round's four Adds are
    summed, and its freshness bits still see every Add and its
    worker."""
    import multiverso_tpu as mv
    ids, deltas = _traffic(66)
    rounds = 2

    def option():
        if kind == "sparse":
            return SparseMatrixTableOption(num_rows=ROWS, num_cols=COLS)
        return MatrixTableOption(num_rows=ROWS, num_cols=COLS,
                                 updater_type=kind)

    def drive(queued: bool):
        _world(mv, "-sync=true")
        try:
            table = mv.MV_CreateTable(option())
            sent = _Staged(mv)
            before = _settled_snapshot()
            for r in range(rounds):
                hold = _engine_held() if queued else contextlib.nullcontext()
                with hold:
                    for w in range(WORKERS):
                        sent.add(table, w, ids[r], deltas[w][r])
                        if not queued:
                            sent.rows()
                    for w in range(WORKERS):
                        # the sparse Get: all that is new to the worker
                        sent.get(table, w, None if kind == "sparse"
                                 else ids[r])
                        if not queued:
                            sent.rows()
            return sent.rows(), _window_moved(before, _settled_snapshot())
        finally:
            mv.MV_ShutDown()

    one_by_one, moved_1 = drive(queued=False)
    queued, moved_q = drive(queued=True)
    assert moved_1["windows"] == 2 * WORKERS * rounds
    assert moved_q["windows"] == rounds
    assert len(queued) == len(one_by_one) == WORKERS * rounds
    for got, want in zip(queued, one_by_one):
        if kind == "sparse":
            assert np.array_equal(got[0], want[0])
            assert np.array_equal(got[1], want[1])
        else:
            assert np.array_equal(got, want)
    if kind == "sparse":
        # worker w's Get of round r: the rows the OTHERS added
        assert sorted(queued[0][0].tolist()) == sorted(ids[0].tolist())
        assert moved_q["get.shared"] == 0
    else:
        assert np.any(queued[-1])
    summed = rounds if kind == "sparse" else 0
    assert moved_q["add.run_merged"] == summed
    assert moved_q["add_run.summed_adds"] == WORKERS * summed
    assert moved_q["add.dispatches"] == (summed or WORKERS * rounds)
    assert moved_1["add.run_merged"] == moved_1["add_run.summed"] == 0


@pytest.mark.parametrize("rows", ["sets_of_their_own", "one_entry_differs",
                                  "another_order", "another_length"])
def test_a_stretch_that_names_different_rows_goes_verb_by_verb(rows):
    """All or nothing a stretch: four queued Adds whose id arrays are
    not one array are four lone Adds (never the stacked run), and every
    Get is still the round's total."""
    import multiverso_tpu as mv
    ids, deltas = _traffic(70)
    per_worker = [ids[0]] * WORKERS
    if rows == "sets_of_their_own":
        per_worker = ids[:WORKERS]
    elif rows == "one_entry_differs":
        other = ids[0].copy()
        other[-1] = ids[1][0]
        per_worker = [ids[0], ids[0], other, ids[0]]
    elif rows == "another_order":
        per_worker = [ids[0], ids[0][::-1].copy(), ids[0], ids[0]]
    else:
        per_worker = [ids[0], ids[0], ids[0], ids[0][:-1]]
    asked = np.unique(np.concatenate([ids[0], *per_worker]))
    _world(mv, "-sync=true")
    try:
        table = mv.MV_CreateTable(MatrixTableOption(num_rows=ROWS,
                                                    num_cols=COLS))
        sent = _Staged(mv)
        before = _settled_snapshot()
        with _engine_held():
            for w in range(WORKERS):
                sent.add(table, w, per_worker[w],
                         deltas[w][0][:len(per_worker[w])])
            for w in range(WORKERS):
                sent.get(table, w, asked)
        rows_got = sent.rows()
        moved = _window_moved(before, _settled_snapshot())
    finally:
        mv.MV_ShutDown()
    want = np.zeros((ROWS, COLS), np.float32)
    for w in range(WORKERS):
        np.add.at(want, per_worker[w], deltas[w][0][:len(per_worker[w])])
    for got in rows_got:
        assert np.array_equal(got, want[asked])
    assert {k: moved[k] for k in (
        "add.run_merged", "add.dispatches", "add_run.summed",
        "add_run.summed_adds", "get.shared", "bsp.rounds", "windows")} == {
        "add.run_merged": 0, "add.dispatches": WORKERS,
        "add_run.summed": 0, "add_run.summed_adds": 0,
        "get.shared": WORKERS - 1, "bsp.rounds": 1, "windows": 1}


@pytest.mark.parametrize("at", [0, 2, 3])
def test_a_stretch_that_fails_validation_answers_every_add_with_it(at):
    """The run contract: a same-rows run validates before it writes, and
    what it raises is the answer to EVERY Add of the stretch (there is no
    verb-by-verb fallback behind an exception). The table is untouched:
    the round's Gets, whose clocks the four Adds did tick, read zeros."""
    import multiverso_tpu as mv
    ids, deltas = _traffic(71)
    _world(mv, "-sync=true")
    try:
        table = mv.MV_CreateTable(MatrixTableOption(num_rows=ROWS,
                                                    num_cols=COLS))
        sent = _Staged(mv)
        before = _settled_snapshot()
        handles = []
        with _engine_held():
            for w in range(WORKERS):
                # worker ``at``'s values are no numbers: the table's
                # conversion raises while the run is being validated
                values = (np.array(["x"] * K) if w == at else deltas[w][0])
                with mv.MV_WorkerContext(w):
                    handles.append(table.AddAsync(
                        {"row_ids": ids[0], "values": values}))
            for w in range(WORKERS):
                sent.get(table, w, ids[0])
        for handle in handles:
            with pytest.raises(ValueError, match="could not convert"):
                table.Wait(handle)
        rows = sent.rows()
        moved = _window_moved(before, _settled_snapshot())
        raw = table.server().raw()
    finally:
        mv.MV_ShutDown()
    assert len(rows) == WORKERS
    for got in rows:
        assert not np.any(got)
    assert not np.any(raw)
    assert (moved["add.dispatches"], moved["add.run_merged"],
            moved["add_run.summed_adds"], moved["bsp.rounds"]) == (0, 0, 0, 1)


@pytest.mark.parametrize("queued", ["whole_rounds", "split_rounds"])
def test_no_program_is_compiled_after_the_first_round(queued):
    """(g) Round 0 goes verb by verb; the rounds behind it are queued
    whole, or so that their stretches hold 3 + 1, 2 + 2 and 1 + 3 verbs:
    no program is traced or compiled for them, whatever sizes the
    stretches take (a stretch of Adds summed is the lone Add's program
    at the lone Add's bucket, and a shared gather is the lone Get's
    program)."""
    import multiverso_tpu as mv
    ids, deltas = _traffic(67)
    _world(mv, "-sync=true")
    try:
        # a shape of its own: no other test's programs are this one's
        table = mv.MV_CreateTable(MatrixTableOption(num_rows=ROWS + 8,
                                                    num_cols=COLS + 1))
        deltas = [[np.pad(d, ((0, 0), (0, 1))) for d in per]
                  for per in deltas]
        sent = _Staged(mv)

        def round_of(r, cuts):
            groups = list(zip((0,) + cuts, cuts + (WORKERS,)))
            for lo, hi in groups:
                with _engine_held():
                    for w in range(lo, hi):
                        sent.add(table, w, ids[r], deltas[w][r])
                sent.rows()
            for lo, hi in groups:
                with _engine_held():
                    for w in range(lo, hi):
                        sent.get(table, w, ids[r])
                sent.rows()

        round_of(0, (1, 2, 3))
        compiled = len(_COMPILES)
        before = _settled_snapshot()
        rounds = ([(), (), ()] if queued == "whole_rounds"
                  else [(3,), (2,), (1,)])
        for r, cuts in enumerate(rounds, start=1):
            round_of(r, cuts)
        built = _COMPILES[compiled:]
        got = sent.rows()
        moved = _window_moved(before, _settled_snapshot())
    finally:
        mv.MV_ShutDown()
    ref = BspRounds(COLS + 1, WORKERS, np.concatenate(ids))
    for r in range(1 + len(rounds)):
        ref.round(r, ids[r], [deltas[w][r] for w in range(WORKERS)])
        for w in range(WORKERS):
            assert np.array_equal(got[WORKERS * r + w],
                                  ref.expect_get(r, ids[r])), (r, w)
    assert built == []
    # the stretches of two or more were summed: 4 + 4 + 4, or 3 + (2 + 2) + 3
    assert moved["add_run.summed_adds"] == (12 if queued == "whole_rounds"
                                            else 10)
    assert moved["add.dispatches"] == (3 if queued == "whole_rounds" else 6)


@pytest.mark.parametrize("late", ["a_get", "the_rounds_last_add"])
def test_what_lands_while_a_window_is_served_joins_it(late):
    """Before a window copies its Gets back it takes what has landed
    since. A Get that lands while the others' gather is in flight
    shares it; the round's last Add that lands while the Adds before it
    are applied is judged next, and its tick drains the Get the clocks
    held. Staged exactly: the late verb is sent from inside the table
    call that serves the window's first verb."""
    import multiverso_tpu as mv
    ids, deltas = _traffic(68)
    last = WORKERS - 1
    _world(mv, "-sync=true")
    try:
        table = mv.MV_CreateTable(MatrixTableOption(num_rows=ROWS,
                                                    num_cols=COLS))
        sent = _Staged(mv)
        srv = table.server()

        def send_late_from(verb, send):
            served = getattr(srv, verb)

            def serve(*args, **kwargs):
                delattr(srv, verb)          # once
                send()
                return served(*args, **kwargs)

            setattr(srv, verb, serve)

        if late == "a_get":
            for w in range(WORKERS):
                with mv.MV_WorkerContext(w):
                    table.AddRows(ids[0], deltas[w][0])
            before = _settled_snapshot()
            send_late_from("ProcessGetAsync",
                           lambda: sent.get(table, last, ids[0]))
            with _engine_held():
                for w in range(last):
                    sent.get(table, w, ids[0])
            want = {"get.shared": WORKERS - 1, "bsp.gets_cached": 0,
                    "bsp.rounds": 0, "window.verbs": WORKERS, "windows": 1}
        else:
            before = _settled_snapshot()
            send_late_from("ProcessAddSameRows", lambda: sent.add(
                table, last, ids[0], deltas[last][0]))
            with _engine_held():
                for w in range(last):
                    sent.add(table, w, ids[0], deltas[w][0])
                sent.get(table, 0, ids[0])
            # the three queued Adds summed, the late one a lone Add
            want = {"add.dispatches": 2, "add.run_merged": 1,
                    "add_run.summed_adds": last, "bsp.gets_cached": 1,
                    "bsp.rounds": 1, "window.verbs": WORKERS + 1,
                    "windows": 1}
        rows = sent.rows()
        moved = _window_moved(before, _settled_snapshot())
    finally:
        mv.MV_ShutDown()
    assert len(rows) == (WORKERS if late == "a_get" else 1)
    for got in rows:
        assert np.array_equal(got, _total(deltas, 0))
    assert {k: moved[k] for k in want} == want


@pytest.mark.parametrize("world", ["one_worker_left", "a_late_fourth_add"])
def test_no_window_waits_for_a_send(world):
    """A window takes what the mailbox holds and never waits for more:
    not for workers that have finished training, and not for a
    straggler's Add (which then opens a window of its own). The engine
    blocks on its mailbox between windows only."""
    import multiverso_tpu as mv
    from multiverso_tpu.zoo import Zoo
    ids, deltas = _traffic(69)
    last = WORKERS - 1
    _world(mv, "-sync=true")
    try:
        table = mv.MV_CreateTable(MatrixTableOption(num_rows=ROWS,
                                                    num_cols=COLS))
        engine = Zoo.Get().server_engine
        inside, waits = [], []
        run_window, pop = engine._run_window, engine.mailbox.Pop

        def watched_window(batch):
            inside.append(batch)
            try:
                run_window(batch)
            finally:
                inside.pop()

        def watched_pop(*args, **kwargs):
            if inside:
                waits.append((args, kwargs))
            return pop(*args, **kwargs)

        engine._run_window, engine.mailbox.Pop = watched_window, watched_pop
        before = _settled_snapshot()
        rows = []
        if world == "one_worker_left":
            for w in range(1, WORKERS):
                _answer(_engine_message(MsgType.Server_Finish_Train, src=w))
            for r in range(3):
                with mv.MV_WorkerContext(0):
                    table.AddRows(ids[0], deltas[0][r])
                    rows.append(table.GetRows(ids[0]).copy())
            want_rows = [sum(deltas[0][:r + 1]) for r in range(3)]
            want = {"windows": 6, "window.verbs": 6, "bsp.gets_cached": 0}
        else:
            sent = _Staged(mv)
            with _engine_held():
                for w in range(last):
                    sent.add(table, w, ids[0], deltas[w][0])
            sent.rows()
            for w in [last] + list(range(last)):
                with mv.MV_WorkerContext(w):
                    if w == last:
                        table.AddRows(ids[0], deltas[w][0])
                    rows.append(table.GetRows(ids[0]).copy())
            want_rows = [_total(deltas, 0)] * WORKERS
            want = {"windows": 2 + WORKERS, "window.verbs": 2 * WORKERS,
                    "bsp.gets_cached": 0, "bsp.rounds": 1}
        moved = _window_moved(before, _settled_snapshot())
    finally:
        mv.MV_ShutDown()
    assert not waits
    for got, want_got in zip(rows, want_rows):
        assert np.array_equal(got, want_got)
    assert {k: moved[k] for k in want} == want


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
