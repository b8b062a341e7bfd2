"""The cell ``mt_sparse_rounds``: its rehearsal ends ``correct`` with the
contract's last line; its comparisons (``reference/sparse_rows.py``) pass
the plain reference and refuse it with one fault at a time (a dropped
mark, a mark set for the adding worker itself, a stale row returned twice
without an Add between, a replay kept in bfloat16); its readers read the
program's spans and counters and read nothing where there are none; a tree
whose sparse Get cannot be warmed is refused before any table is made."""

import json

import ml_dtypes
import numpy as np
import pytest

from benchmark.harness import cells
from benchmark.harness.run_record import Run
from benchmark.layer_metrics import (sparse_read_ms_per_get,
                                     sparse_rows_per_get,
                                     sparse_select_ms_per_get,
                                     sparse_select_pct)
from benchmark.reference import sparse_rows
from benchmark.tests.test_last_line import _run

CELL = "mt_sparse_rounds"
ROWS, COLS, WORKERS, K = 400, 6, 4, 12


@pytest.mark.parametrize("traced", [0, 1])
def test_rehearsal_ends_correct_with_the_contract_line(traced):
    res = _run("--workload", CELL, "--seed", str(2**31 + 31), "--seconds",
               "1", "--trace", str(traced), "--rehearsal")
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3 and line["device"]["platform"] == "cpu"
    cell = cells.load_cell(CELL)
    allowed = {m["name"] for m in (cell.per_layer if traced
                                   else cell.end_to_end)}
    assert set(line["metrics"]) <= allowed
    if not traced:
        assert set(line["metrics"]) == {"table_rows_per_s", "setup_s"}
        return
    got = line["metrics"]
    assert got["tables_window_compiles"]["value"] == 0.0
    for name in ("sparse_select_ms_per_get", "sparse_select_pct",
                 "sparse_read_ms_per_get", "sparse_rows_per_get",
                 "verbs_per_window", "add_dispatches_per_verb",
                 "window_merge_ms_mean", "verb_queue_wait_ms_mean"):
        assert got[name]["value"] > 0, name
    # the sparse Get records no server.table.get.dispatch: the metric
    # that reads it is not this cell's
    assert "window_dispatch_ms_mean" not in allowed


def test_the_lists_the_cell_joined():
    bench = cells.load_benchmark()
    lists = {m["name"]: m.get("workloads")
             for m in bench["end_to_end"] + bench["per_layer"]}
    for name in ("table_rows_per_s", "tables_window_compiles",
                 "tables_host_cpu_cores", "verbs_per_window",
                 "engine_window_ms_mean", "verb_p95_ms",
                 "add_dispatches_per_verb", "verb_queue_wait_ms_mean",
                 "window_finalize_ms_mean", "window_merge_ms_mean",
                 "tables_custom_call_busy_pct", "tables_top_op_busy_pct",
                 "tables_device_idle_pct"):
        assert lists[name][-1] == CELL, name
    for name in ("sparse_select_ms_per_get", "sparse_select_pct",
                 "sparse_read_ms_per_get", "sparse_rows_per_get"):
        assert lists[name] == [CELL]
    assert CELL not in lists["window_dispatch_ms_mean"]


# -- the comparisons, on numpy ----------------------------------------------

class _DropsAMark(sparse_rows.SparseRows):
    """Forgets, at every Add, to mark its first row stale for one other
    worker. (A later Add of the same row by another worker hides one such
    fault, in the cell as here: the row then does reach the worker.)"""

    def add(self, worker, ids, deltas):
        fresh = self.up_to_date[(worker + 1) % WORKERS, int(ids[0])]
        super().add(worker, ids, deltas)
        self.up_to_date[(worker + 1) % WORKERS, int(ids[0])] = fresh


class _MarksTheAdder(sparse_rows.SparseRows):
    def add(self, worker, ids, deltas):
        super().add(worker, ids, deltas)
        self.up_to_date[worker, np.asarray(ids)] = False


class _ReturnsTwice(sparse_rows.SparseRows):
    """Once, a Get leaves the rows it returned marked stale."""
    done = False

    def get(self, worker, ids=None):
        out, rows = super().get(worker, ids)
        if not self.done and len(out) > 1:
            self.done = True
            self.up_to_date[worker, out] = False
        return out, rows


def _rounds(table_type, seed: int):
    """The cell in small on a plain table: workers take turns, a verb at a
    time in a seeded order, at rounds of Get-all / Add / Get-all; then one
    more Get-all a worker. -> (table, what each worker's Gets returned,
    every Add as (worker, ids, delta))."""
    rng = np.random.default_rng(seed)
    table = table_type(ROWS, COLS, WORKERS)
    returned = [[] for _ in range(WORKERS)]
    adds = []
    at = [0] * WORKERS           # the verb each worker is at, of 3 a round
    for _ in range(3 * 10 * WORKERS):
        w = int(rng.integers(0, WORKERS))
        if at[w] % 3 == 1:
            ids = rng.choice(ROWS, K, replace=False).astype(np.int32)
            delta = rng.integers(-1000, 1001, (K, COLS)).astype(np.float32)
            table.add(w, ids, delta)
            adds.append((w, ids, delta))
        else:
            returned[w].append(table.get(w)[0])
        at[w] += 1
    for w in range(WORKERS):
        returned[w].append(table.get(w)[0])
    return table, returned, adds


def _verdict(table, returned, adds):
    """The runner's comparisons (ii) and (i) on such a run: -> per worker
    (over, missed), and whether the table equals the replay."""
    sample = np.arange(ROWS, dtype=np.int32)
    cover = [sparse_rows.coverage(
        returned[w], [(ids, 1) for v, ids, _ in adds if v != w], ROWS)
        for w in range(WORKERS)]
    exact = np.array_equal(table.data, sparse_rows.replay_rows(
        sample, COLS, [(ids, d, 1) for _, ids, d in adds]))
    return cover, exact


@pytest.mark.parametrize("seed", range(4))
def test_the_plain_reference_passes(seed):
    cover, exact = _verdict(*_rounds(sparse_rows.SparseRows, seed))
    assert cover == [(0, 0)] * WORKERS and exact


def test_a_dropped_mark_is_refused():
    cover, exact = _verdict(*_rounds(_DropsAMark, 1))
    assert exact and all(over == 0 for over, _ in cover)
    assert sum(missed for _, missed in cover) > 0


def test_a_mark_for_the_adder_itself_is_refused():
    cover, _ = _verdict(*_rounds(_MarksTheAdder, 2))
    assert all(over > 0 for over, _ in cover)


def test_a_row_returned_twice_is_refused():
    cover, _ = _verdict(*_rounds(_ReturnsTwice, 3))
    assert sum(over for over, _ in cover) > 0


def test_a_replay_in_bfloat16_is_refused():
    table, _, adds = _rounds(sparse_rows.SparseRows, 4)
    sample = np.arange(ROWS, dtype=np.int32)
    replayed = [(ids, d, 3) for _, ids, d in adds]
    exact = sparse_rows.replay_rows(sample, COLS, replayed)
    low = sparse_rows.replay_rows(sample, COLS, replayed,
                                  dtype=ml_dtypes.bfloat16)
    assert np.array_equal(exact, 3 * table.data)
    named = np.unique(np.concatenate([ids for _, ids, _ in adds]))
    assert np.mean(low[named] != exact[named]) > 0.5


def test_a_lone_row_zero_is_not_a_returned_row():
    nothing = [np.zeros(1, np.int32)]
    assert sparse_rows.coverage(nothing, [], ROWS) == (0, 0)
    # others added row 0 and row 5: row 5 was never returned
    assert sparse_rows.coverage(
        nothing, [(np.array([0, 5], np.int32), 1)], ROWS) == (0, 1)
    assert sparse_rows.coverage(
        [np.array([5], np.int32), np.array([5], np.int32)],
        [(np.array([5], np.int32), 1)], ROWS) == (1, 0)


# -- the readers --------------------------------------------------------------

def _traced(host, **kw) -> Run:
    return Run(cell=None, seed=0, seconds=1.0, traced=True, rehearsal=False,
               trace={"devices": [], "host": host, "window": [1000, 2000]},
               **kw)


def test_readers_on_a_hand_made_trace():
    host = [["server.table.sparse.get.select", 1000, 10, "engine"],
            ["server.table.sparse.get.read", 1010, 90, "engine"],
            ["server.table.sparse.add.mark", 1100, 5, "engine"],
            ["server.table.sparse.get.select", 1500, 30, "engine"],
            ["server.table.sparse.get.read", 1530, 110, "engine"],
            ["server.table.sparse.get.select", 1990, 20, "engine"]]
    counters = ({"table.sparse.get.rows": {"value": 100.0}},
                {"table.sparse.get.rows": {"value": 700.0},
                 "table.sparse.get.empty": {"value": 1.0}})
    run = _traced(host, counters_before=counters[0],
                  counters_after=counters[1], window={"gets": 4})
    # the third select reaches 10 ns into the window
    assert sparse_select_ms_per_get.read(run) == pytest.approx(50e-6 / 3)
    assert sparse_select_pct.read(run) == pytest.approx(5.0)
    assert sparse_read_ms_per_get.read(run) == pytest.approx(100e-6)
    assert sparse_rows_per_get.read(run) == pytest.approx(150.0)


@pytest.mark.parametrize("reader", [sparse_select_ms_per_get,
                                    sparse_select_pct,
                                    sparse_read_ms_per_get,
                                    sparse_rows_per_get],
                         ids=lambda r: r.__name__.rsplit(".", 1)[-1])
def test_a_program_without_the_instrument_reads_as_nothing(reader):
    """A tree before this cell has neither the spans nor the counters."""
    old = {"server.window.verbs": {"type": "counter", "value": 3.0}}
    bare = [["bench.window", 0, 10, "main"]]
    for run in (_traced(bare, counters_before=old, counters_after=old,
                        window={"gets": 4}),
                Run(cell=None, seed=0, seconds=1.0, traced=False,
                    rehearsal=False, counters_before=old,
                    counters_after=old, window={"gets": 4})):
        assert reader.read(run) is None


def test_a_tree_without_a_bounded_read_is_refused_early(monkeypatch):
    """The runner needs ``read_buckets()`` to warm the Gets' shapes; a
    tree without it fails at once, before the world and the table."""
    import multiverso_tpu as mv
    from benchmark.runners import table_sparse_rounds
    from multiverso_tpu.tables.sparse_matrix_table import (
        SparseMatrixServerTable)
    monkeypatch.delattr(SparseMatrixServerTable, "read_buckets")
    inits = []
    monkeypatch.setattr(mv, "MV_Init", lambda *a, **k: inits.append(a))
    runner = table_sparse_rounds.Runner(cells.load_cell(CELL).sized(True),
                                        1, True)
    with pytest.raises(RuntimeError, match="read_buckets"):
        runner.setup("/nonexistent")
    assert not inits
    runner.close()
