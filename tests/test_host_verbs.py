"""The host-plane verbs of a float32 MatrixTable under the two linear
updaters (``default`` adds, ``sgd`` subtracts), through the row programs
the cell ``mt_host_verbs`` runs: ``_merged_add_rows`` under
``ProcessAddRun``, ``_device_ids`` / ``_device_opt``, the gather of
``ProcessGetAsync`` and the cut of its bucket's pad, ``_update_full``.
Deltas are whole numbers, so a table must equal a numpy replay bit for
bit, in any order of summation.
"""

import threading
import time

import numpy as np
import pytest

from multiverso_tpu.message import Message, MsgType
from multiverso_tpu.tables import MatrixTableOption, matrix_table
from multiverso_tpu.telemetry import metrics
from multiverso_tpu.updaters.base import GetOption
from multiverso_tpu.zoo import Zoo

#: the benchmark's table shape in small: 50 logical columns in one lane tile
ROWS, COLS = 200, 50


@pytest.fixture()
def world():
    import multiverso_tpu as mv
    mv.MV_Init(["-num_workers=2"])
    yield mv
    mv.MV_ShutDown()


def _counter(name: str) -> float:
    return metrics.snapshot().get(name, {}).get("value", 0)


def _held_window(table, submit):
    """The replies to the verbs ``submit()`` sends (it returns their
    handles), which reach the engine as ONE window: a message ahead of
    them holds the engine until all are queued."""
    gate = threading.Event()
    Zoo.Get().SendToServer(Message(
        msg_type=MsgType.Request_StoreLoad,
        payload={"fn": lambda: gate.wait(60)}))
    handles = submit()
    gate.set()
    return [table.Wait(h) for h in handles]


def _one_window(table, batches):
    """Tracked AddRows that reach the engine as ONE window."""
    _held_window(table, lambda: [table.AddAsyncHandle(delta, ids)
                                 for ids, delta in batches])


def _batch(rng, n: int):
    """n ids, a third of them distinct (repeats inside the payload)."""
    uniq = rng.choice(ROWS, max(n // 3, 1), replace=False)
    ids = rng.permutation(np.concatenate(
        [uniq, rng.choice(uniq, n - len(uniq))])).astype(np.int32)
    return ids, rng.integers(-3, 4, (n, COLS)).astype(np.float32)


def _merged_window(table, replay, rng, sign):
    first = _batch(rng, 24)
    # the same id set again (repeats across payloads), then another
    batches = [first, (first[0], _batch(rng, 24)[1]), _batch(rng, 24)]
    merged = _counter("server.add.run_merged")
    _one_window(table, batches)
    assert _counter("server.add.run_merged") - merged == 1
    for ids, delta in batches:
        np.add.at(replay, ids, sign * delta)


def _mixed_shapes(table, replay, rng, sign):
    batches = [_batch(rng, 8), _batch(rng, 12), _batch(rng, 8)]
    merged = _counter("server.add.run_merged")
    _one_window(table, batches)
    # a compile a window shape: the run declines
    assert _counter("server.add.run_merged") == merged
    for ids, delta in batches:
        np.add.at(replay, ids, sign * delta)


def _get_rows(table, replay, rng, sign):
    ids, delta = _batch(rng, 40)
    table.AddRows(ids, delta)
    np.add.at(replay, ids, sign * delta)
    for n in (64, 40):      # at its bucket (no slice to cut) and under it
        ask = rng.choice(ROWS, n).astype(np.int32)
        got = table.GetRows(ask)
        assert got.shape == (n, COLS) and got.dtype == np.float32
        np.testing.assert_array_equal(got, replay[ask])
    np.testing.assert_array_equal(table.Get(), replay)


def _whole_add(table, replay, rng, sign):
    for _ in range(2):
        full = rng.integers(-3, 4, (ROWS, COLS)).astype(np.float32)
        table.Add(full)
        replay += sign * full


def _multiget(table, replay, rng, sign):
    ids = np.arange(3, dtype=np.int32)
    other = np.array([7, 5], np.int32)
    got = table.MultiGet([{"row_ids": ids}, {"row_ids": ids},
                          {"row_ids": other}])
    for member, asked in zip(got, (ids, ids, other)):
        np.testing.assert_array_equal(member, replay[asked])
        assert member.flags.writeable and member.flags.owndata
    got[0][:] = 99.0        # a member's rows are its own
    got[2][:] = 99.0
    np.testing.assert_array_equal(got[1], replay[ids])
    np.testing.assert_array_equal(table.GetRows(other), replay[other])


@pytest.mark.parametrize("updater_type", ["default", "sgd"])
@pytest.mark.parametrize("case", [_merged_window, _mixed_shapes, _get_rows,
                                  _whole_add, _multiget],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_host_verb_equals_replay(world, case, updater_type):
    rng = np.random.default_rng(5)
    replay = rng.integers(-8, 9, (ROWS, COLS)).astype(np.float32)
    init = replay.copy()
    table = world.MV_CreateTable(MatrixTableOption(
        num_rows=ROWS, num_cols=COLS, updater_type=updater_type,
        initializer=lambda shape: init))
    case(table, replay, rng, -1.0 if updater_type == "sgd" else 1.0)
    np.testing.assert_array_equal(table.GetRows(np.arange(ROWS)), replay)
    np.testing.assert_array_equal(table.server().raw(), replay)


# -- where the pad of a Get's bucket is dropped -----------------------------
# A gather returns its bucket; the first n rows are the reply. The bucket
# crosses whole and a host view drops the pad (one launch a Get) unless the
# pad is over ``matrix_table._HOST_CUT_PAD_BYTES``, when a slice program
# drops it first (two launches).

def _table(world, rng):
    """A table of whole numbers, and its initial rows."""
    init = rng.integers(-8, 9, (ROWS, COLS)).astype(np.float32)
    table = world.MV_CreateTable(MatrixTableOption(
        num_rows=ROWS, num_cols=COLS, initializer=lambda shape: init))
    return table, init


GET_PATHS = {
    "engine": lambda table, srv, ids: table.GetRows(ids),
    "process_get": lambda table, srv, ids: srv.ProcessGet(GetOption(),
                                                          row_ids=ids),
    "read_rows_union": lambda table, srv, ids: srv._read_rows_union(ids),
}

#: n, the pad bytes the constant is patched down to (None: as it is), and
#: the side that drops the pad
PAD_CASES = {
    "at_its_bucket": (64, None, None),
    "one_under_its_bucket": (63, None, "host"),
    "10000_under_10240": (10_000, None, "host"),
    "pad_over_the_constant": (40, 24 * COLS * 4 - 1, "device"),
}


@pytest.mark.parametrize("path", GET_PATHS)
@pytest.mark.parametrize("pad_case", PAD_CASES)
def test_get_drops_its_buckets_pad(world, monkeypatch, pad_case, path):
    n, limit, side = PAD_CASES[pad_case]
    if limit is not None:
        monkeypatch.setattr(matrix_table, "_HOST_CUT_PAD_BYTES", limit)
    rng = np.random.default_rng(11)
    table, init = _table(world, rng)
    srv = table.server()
    ids = rng.choice(ROWS, n).astype(np.int32)
    names = ("table.device.calls", "table.get.host_cuts",
             "table.get.device_cuts")
    before = [_counter(name) for name in names]
    got = GET_PATHS[path](table, srv, ids)
    stepped = [_counter(name) - was for name, was in zip(names, before)]
    assert got.shape == (n, COLS) and got.dtype == np.float32
    np.testing.assert_array_equal(got, srv.raw()[ids])
    assert stepped == [2 if side == "device" else 1,
                       int(side == "host"), int(side == "device")]
    if side is not None:    # the first cut registers both counters
        assert {"table.get.host_cuts", "table.get.device_cuts"} <= set(
            metrics.snapshot())


def test_add_between_two_gets_of_a_window(world):
    """Get, Add, Get of the same rows in ONE window: the first reply is
    the bucket gathered before the Add, copied back after the Add's
    program donated the state it was gathered from."""
    rng = np.random.default_rng(13)
    table, init = _table(world, rng)
    ids = rng.choice(ROWS, 40, replace=False).astype(np.int32)
    delta = rng.integers(1, 4, (40, COLS)).astype(np.float32)

    def windows():
        return metrics.snapshot().get("server.window.latency_s",
                                      {}).get("count", 0)
    host_cuts, was = _counter("table.get.host_cuts"), windows()
    verbs = _counter("server.window.verbs")
    first, _, second = _held_window(table, lambda: [
        table.GetAsyncHandle(ids), table.AddAsyncHandle(delta, ids),
        table.GetAsyncHandle(ids)])
    # the engine counts a window, then its verbs, after the replies
    deadline = time.monotonic() + 30
    while (_counter("server.window.verbs") - verbs < 3
           and time.monotonic() < deadline):
        time.sleep(0.01)
    assert _counter("server.window.verbs") - verbs == 3
    assert windows() - was == 1
    assert _counter("table.get.host_cuts") - host_cuts == 2
    np.testing.assert_array_equal(first, init[ids])
    np.testing.assert_array_equal(second, init[ids] + delta)
    assert first.shape == second.shape == (40, COLS)


# -- the same-rows run ------------------------------------------------------
# A run of Adds whose payloads name ONE id array is summed on the host and
# applied as one lone Add (``ProcessAddSameRows``; ``ProcessAddRun`` tries
# it first, and the BSP engine offers its stretches nothing else).

K = 24


def _shared_ids(rng):
    """K ids, a third of them distinct: repeats inside the shared set."""
    return _batch(rng, K)[0]


def _payloads(rng, ids, n: int, whole: bool, worker_of=lambda i: i % 2):
    from multiverso_tpu.updaters.base import AddOption
    out = []
    for i in range(n):
        delta = (rng.integers(-3, 4, (K, COLS)) if whole
                 else rng.standard_normal((K, COLS))).astype(np.float32)
        delta.setflags(write=False)     # a write to a payload raises
        out.append({"row_ids": ids, "values": delta,
                    "option": AddOption(worker_id=worker_of(i))})
    ids.setflags(write=False)
    return out


@pytest.mark.parametrize("entry", ["ProcessAddSameRows", "ProcessAddRun"])
@pytest.mark.parametrize("deltas", ["whole_numbers", "random"])
@pytest.mark.parametrize("n", [2, 3, 4])
def test_same_rows_run_equals_the_adds_one_by_one(world, n, deltas, entry):
    rng = np.random.default_rng(17 + n)
    summed, init = _table(world, rng)
    one_by_one, _ = _table(world, np.random.default_rng(17 + n))
    ids = _shared_ids(rng)
    payloads = _payloads(rng, ids, n, whole=deltas == "whole_numbers")
    kept = [p["values"].copy() for p in payloads]
    names = ("table.add_run.summed", "table.add_run.summed_adds",
             "table.device.calls", "table.device.h2d_copies",
             "table.device.h2d_bytes")

    def moved_by(call):
        before = [_counter(name) for name in names]
        call()
        return [_counter(name) - was for name, was in zip(names, before)]

    runs, adds, *crossed = moved_by(
        lambda: getattr(summed.server(), entry)(payloads))
    assert (runs, adds) == (1, n)
    # what crosses is ONE lone Add's, whatever n
    lone = moved_by(lambda: one_by_one.server().ProcessAdd(**payloads[0]))
    assert crossed == lone[2:] and lone[:2] == [0, 0]
    assert crossed[2] < 2 * K * COLS * 4
    for p in payloads[1:]:
        one_by_one.server().ProcessAdd(**p)
    got, want = summed.server().raw(), one_by_one.server().raw()
    if deltas == "whole_numbers":
        np.testing.assert_array_equal(got, want)
        replay = init.copy()
        for p in payloads:
            np.add.at(replay, ids, p["values"])
        np.testing.assert_array_equal(got, replay)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for p, was in zip(payloads, kept):
        np.testing.assert_array_equal(p["values"], was)


def _declines(world, monkeypatch, rng, case):
    """-> (table, its initial rows, payloads the same-rows run declines)."""
    table, init = _table(world, rng)
    ids = rng.choice(ROWS, K, replace=False).astype(np.int32)
    payloads = _payloads(rng, ids, 3, whole=True)
    other = ids.copy()
    if case == "one_entry_differs":
        other[-1] = (other[-1] + 1) % ROWS
        payloads[2]["row_ids"] = other
    elif case == "another_length":
        payloads[1]["row_ids"] = ids[:-1]
        payloads[1]["values"] = payloads[1]["values"][:-1]
    elif case == "another_order":
        payloads[1]["row_ids"] = ids[::-1].copy()
    elif case == "compressed":
        payloads[2]["compressed"] = {"kind": "sparse", "row_ids": ids}
    elif case == "whole_table":
        payloads[0] = {"row_ids": None, "option": None,
                       "values": np.ones((ROWS, COLS), np.float32)}
    elif case == "id_out_of_range":
        other[0] = ROWS
        for p in payloads:
            p["row_ids"] = other
    elif case == "values_of_another_size":
        payloads[2]["values"] = payloads[2]["values"][:-1]
    elif case == "a_lone_add":
        payloads = payloads[:1]
    elif case == "non_linear_updater":
        table = world.MV_CreateTable(MatrixTableOption(
            num_rows=ROWS, num_cols=COLS, updater_type="adagrad",
            initializer=lambda shape: init))
    elif case == "two_processes":
        monkeypatch.setattr(matrix_table.multihost, "world_size", lambda: 2)
    else:
        raise AssertionError(case)
    return table, init, payloads


@pytest.mark.parametrize("case", [
    "one_entry_differs", "another_length", "another_order", "compressed",
    "whole_table", "id_out_of_range", "values_of_another_size",
    "a_lone_add", "non_linear_updater", "two_processes"])
def test_same_rows_run_declines_and_leaves_the_table(world, monkeypatch,
                                                     case):
    table, init, payloads = _declines(world, monkeypatch,
                                      np.random.default_rng(23), case)
    srv = table.server()
    names = ("table.device.calls", "table.device.h2d_copies",
             "table.add_run.summed", "table.add_run.summed_adds")
    before = [_counter(name) for name in names]
    assert srv.ProcessAddSameRows(payloads) is False
    monkeypatch.undo()
    assert [_counter(name) for name in names] == before
    np.testing.assert_array_equal(srv.raw(), init)


def test_a_run_of_mixed_id_sets_is_stacked_as_before(world):
    """``ProcessAddRun`` falls through at the first id array that
    differs: the stacked run's program, every payload's bytes across."""
    rng = np.random.default_rng(29)
    table, init = _table(world, rng)
    ids = rng.choice(ROWS, K, replace=False).astype(np.int32)
    payloads = _payloads(rng, ids, 3, whole=True)
    payloads[2]["row_ids"] = rng.choice(ROWS, K, replace=False).astype(
        np.int32)
    h2d = _counter("table.device.h2d_bytes")
    summed = _counter("table.add_run.summed")
    assert table.server().ProcessAddRun(payloads) is True
    assert _counter("table.add_run.summed") == summed
    # the stack is a power of two of batches: four for three
    assert _counter("table.device.h2d_bytes") - h2d >= 4 * K * COLS * 4
    for p in payloads:
        np.add.at(init, p["row_ids"], p["values"])
    np.testing.assert_array_equal(table.server().raw(), init)


@pytest.mark.parametrize("entry", ["ProcessAddSameRows", "ProcessAddRun"])
def test_sparse_table_notes_every_payload_of_a_same_rows_run(world, entry):
    """SparseMatrixTable's freshness bits (and the publish journal behind
    the same hook) see every Add of a summed run in message order with
    its own option, as they do verb by verb."""
    from multiverso_tpu.tables import SparseMatrixTableOption
    rng = np.random.default_rng(31)
    tables = [world.MV_CreateTable(SparseMatrixTableOption(
        num_rows=ROWS, num_cols=COLS)) for _ in range(2)]
    summed, one_by_one = (t.server() for t in tables)
    ids = rng.choice(ROWS, K, replace=False).astype(np.int32)
    payloads = _payloads(rng, ids, 3, whole=True,
                         worker_of=lambda i: (1, 0, 1)[i])
    for srv in (summed, one_by_one):    # every worker fresh on every row
        for w in range(2):
            srv.ProcessGet(GetOption(worker_id=w))
    noted = []
    note = summed._note_add_parts

    def recorded(option, parts):
        noted.append((option.worker_id, [np.array(p) for p in parts]))
        note(option, parts)

    summed._note_add_parts = recorded
    assert getattr(summed, entry)(payloads) is True
    for p in payloads:
        one_by_one.ProcessAdd(**p)
    assert [w for w, _ in noted] == [1, 0, 1]
    for _, parts in noted:
        assert len(parts) == 1
        np.testing.assert_array_equal(parts[0], ids)
    np.testing.assert_array_equal(summed.up_to_date, one_by_one.up_to_date)
    np.testing.assert_array_equal(summed.raw(), one_by_one.raw())
    # the last Add was worker 1's: worker 0 is stale on the rows, and
    # worker 1 too (worker 0 added between its Adds)
    for w in range(2):
        got_ids, rows = summed.ProcessGet(GetOption(worker_id=w))
        assert sorted(got_ids.tolist()) == sorted(ids.tolist())
