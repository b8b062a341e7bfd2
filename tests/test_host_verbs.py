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
