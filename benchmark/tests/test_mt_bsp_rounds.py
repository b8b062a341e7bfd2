"""The cell ``mt_bsp_rounds``: its rehearsal runs to its end and is
``correct`` with the contract's last line; its files and entries exist,
asked for by name and by membership (never by position: a later PR
appends); its comparison (``reference/bsp_rounds.py``, every tolerance 0)
refuses a table kept in bfloat16 or float16 and a Get that lacks another
worker's Add; its five readers read the program's spans and instruments
and read nothing where there are none (the parent's side of a pair)."""

import json

import ml_dtypes
import numpy as np
import pytest

from benchmark.harness import cells
from benchmark.harness.run_record import Run
from benchmark.layer_metrics import (bsp_cached_adds_pct, bsp_cached_gets_pct,
                                     bsp_drain_ms_mean, bsp_get_hold_ms_mean,
                                     bsp_round_ms_mean)
from benchmark.reference import bsp_rounds
from benchmark.tests.test_last_line import _run

CELL, CONFIG, MIX = ("mt_bsp_rounds", "matrix-perf-bsp-9m-50",
                     "rounds_add1pct_get_4w_bsp")
NEW = {"bsp_round_ms_mean": ("ms", "program_counter"),
       "bsp_get_hold_ms_mean": ("ms", "program_span"),
       "bsp_drain_ms_mean": ("ms", "program_span"),
       "bsp_cached_gets_pct": ("%", "program_counter"),
       "bsp_cached_adds_pct": ("%", "program_counter")}
JOINED = ("tables_window_compiles", "tables_host_cpu_cores",
          "tables_custom_call_busy_pct", "tables_top_op_busy_pct",
          "tables_device_idle_pct", "device_calls_per_op", "h2d_mb_per_op",
          "table_place_pct", "table_call_pct", "verb_p95_ms",
          "add_dispatches_per_verb")
#: the windowed engine's metrics (BSP has no window), and the two whose
#: reader finds nothing on the parent of PR 50 (its blocking Get's wait
#: and copy back were ``actor.server.dispatch.*``): a listed metric has to
#: print on both sides of a pair
NOT_JOINED = ("verbs_per_window", "engine_window_ms_mean",
              "verb_queue_wait_ms_mean", "window_finalize_ms_mean",
              "window_dispatch_ms_mean", "window_merge_ms_mean",
              "table_wait_pct", "table_take_pct")


@pytest.mark.parametrize("traced", [0, 1])
def test_rehearsal_ends_correct_with_the_contract_line(traced):
    res = _run("--workload", CELL, "--seed", str(2**31 + 50), "--seconds",
               "1", "--trace", str(traced), "--rehearsal")
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 8 * 2 and line["device"]["platform"] == "cpu"
    assert line["attempted"] % 8 == 0       # whole rounds of four workers
    cell = cells.load_cell(CELL)
    allowed = {m["name"] for m in (cell.per_layer if traced
                                   else cell.end_to_end)}
    assert set(line["metrics"]) <= allowed
    if not traced:
        assert set(line["metrics"]) == {"table_rows_per_s", "setup_s"}
        return
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["tables_window_compiles"] == 0.0
    # every metric the cell lists prints, the five new ones among them
    # (the CPU backend reports no memory peak)
    assert set(got) == allowed - {"hbm_peak_gb"}
    assert got["bsp_round_ms_mean"] > 0
    assert 0.0 <= got["bsp_cached_gets_pct"] <= 75.0
    assert 0.0 <= got["bsp_cached_adds_pct"] <= 75.0
    assert got["bsp_get_hold_ms_mean"] >= 0 and got["bsp_drain_ms_mean"] >= 0
    assert got["add_dispatches_per_verb"] == 1.0    # every Add a lone one


def test_the_files_and_the_entries_by_name():
    bench = cells.load_benchmark()
    config = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(config) == 1 and config[0]["reduced"] == ["rows"]
    body = cells._load(config[0]["file"])
    assert body["runner"] == "table_bsp_rounds"
    assert body["world_flags"] == ["-sync=true"]
    assert (body["rows"], body["cols"]) == (9_000_000, 50)
    same = cells._load("benchmark/configs/matrix-perf-9m-50.json")
    assert (body["rows"], body["cols"], body["cuts"]) == (
        same["rows"], same["cols"], same["cuts"])
    entry = [w for w in bench["workloads"] if w["name"] == CELL]
    assert entry == [{"name": CELL, "config": CONFIG, "traffic": MIX,
                      "chips": 1, "why": entry[0]["why"]}]
    cell = cells.load_cell(CELL)
    assert cell.traffic["workers"] == 4 and cell.chips == 1
    assert cell.workload["nominal_rows_per_s"] > 0
    limits = {k: v for k, v in cell.workload["tolerance"].items()
              if not k.endswith("_why")}
    assert limits and set(limits.values()) == {0}
    assert all(cell.workload["tolerance"][k + "_why"] for k in limits)
    assert cells.load_runner(body["runner"]).Runner
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 2


def test_the_metrics_by_name_and_membership():
    bench = cells.load_benchmark()
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    assert CELL in by_name["table_rows_per_s"]["workloads"]
    for name, (unit, source) in NEW.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "worker verbs and engine",
            "moves": "table_rows_per_s", "workloads": [CELL]}
        assert cells.load_reader("layer_metrics", name)
    for name in JOINED:
        assert CELL in by_name[name]["workloads"], name
    for name in NOT_JOINED:
        assert CELL not in by_name[name]["workloads"], name
    reported = {m["name"] for m in cells.load_cell(CELL).per_layer}
    assert reported == set(NEW) | set(JOINED) | {
        "setup_compiled_programs", "hbm_peak_gb", "tables_create_s"}
    # no other cell reports the five
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert not set(NEW) & {
                m["name"] for m in cells.load_cell(w["name"]).per_layer}


# -- the comparison, on numpy -----------------------------------------------

ROWS, COLS, WORKERS, K = 400, 6, 4, 12


def _rounds(seed: int, rounds: int = 6):
    rng = np.random.default_rng(seed)
    ids = [rng.choice(ROWS, K, replace=False).astype(np.int32)
           for _ in range(rounds)]
    deltas = [[rng.integers(-1000, 1001, (K, COLS)).astype(np.float32)
               for _ in range(WORKERS)] for _ in range(rounds)]
    ref = bsp_rounds.BspRounds(COLS, WORKERS, np.concatenate(ids))
    return ids, deltas, ref


def test_the_replay_round_by_round():
    ids, deltas, ref = _rounds(1)
    dense = np.zeros((ROWS, COLS), np.float32)
    for r, (i, d) in enumerate(zip(ids, deltas)):
        ref.round(r, i, d)
        for delta in d:
            dense[i] += delta
        assert np.array_equal(ref.expect_get(r, i), dense[i])
        # a Get that lacks the last worker's Add of its round is refused
        assert not np.array_equal(ref.expect_get(r, i), dense[i] - d[-1])
    assert np.array_equal(ref.table_rows(ref.ids), dense[ref.ids])


@pytest.mark.parametrize("dtype", [ml_dtypes.bfloat16, np.float16])
def test_a_two_byte_table_is_refused(dtype):
    """Deltas reach 1,000 and a round sums four: bfloat16 holds whole
    numbers to 256 and float16 to 2,048, so a table kept in either is off
    within a round or two and every limit is 0."""
    ids, deltas, ref = _rounds(2)
    small = np.zeros((ROWS, COLS), dtype)
    off = []
    for r, (i, d) in enumerate(zip(ids, deltas)):
        ref.round(r, i, d)
        for delta in d:
            small[i] = (small[i].astype(np.float32) + delta).astype(dtype)
        off.append(not np.array_equal(ref.expect_get(r, i),
                                      small[i].astype(np.float32)))
    assert off[0] or off[1]
    assert all(off[1:])


# -- the five readers, on hand-made runs ------------------------------------

def _made(before, after, host=None, traced=True):
    run = Run(cell=None, seed=0, seconds=1.0, traced=traced, rehearsal=False)
    run.counters_before, run.counters_after = before, after
    if traced:
        run.trace = {"devices": [], "host": host or [],
                     "window": [1_000, 11_000], "stat_keys": []}
    return run


def _counter(v):
    return {"type": "counter", "value": float(v)}


def _with(gets=0, gets_cached=0, adds=0, adds_cached=0, rounds=0,
          round_s=(0, 0.0)):
    return {"server.bsp.gets": _counter(gets),
            "server.bsp.gets_cached": _counter(gets_cached),
            "server.bsp.adds": _counter(adds),
            "server.bsp.adds_cached": _counter(adds_cached),
            "server.bsp.rounds": _counter(rounds),
            "server.bsp.round_s": {"type": "histogram", "count": round_s[0],
                                   "sum": round_s[1]}}


READERS = (bsp_round_ms_mean, bsp_get_hold_ms_mean, bsp_drain_ms_mean,
           bsp_cached_gets_pct, bsp_cached_adds_pct)


def test_the_readers_on_a_window_of_rounds():
    before = _with(gets=12, gets_cached=9, adds=12, adds_cached=2, rounds=3,
                   round_s=(3, 0.050))
    after = _with(gets=52, gets_cached=39, adds=52, adds_cached=22,
                  rounds=13, round_s=(13, 0.250))
    host = [["server.bsp.get_hold", 2_000, 3_000, "engine"],
            ["server.bsp.get_hold", 2_500, 2_500, "engine"],
            ["server.bsp.drain", 5_000, 1_000, "engine"],
            # a span that crosses the window's end counts for its part
            ["server.bsp.drain", 10_500, 1_000, "engine"],
            ["server.table.get", 5_000, 400, "engine"]]
    run = _made(before, after, host)
    assert bsp_round_ms_mean.read(run) == pytest.approx(20.0)
    assert bsp_cached_gets_pct.read(run) == pytest.approx(75.0)
    assert bsp_cached_adds_pct.read(run) == pytest.approx(50.0)
    assert bsp_get_hold_ms_mean.read(run) == pytest.approx(
        1e3 * 5_500e-9 / 40)
    assert bsp_drain_ms_mean.read(run) == pytest.approx(1e3 * 1_500e-9 / 10)


def test_lock_step_reads_zero_and_not_nothing():
    """Workers in lock step: no verb was cached, so the trace holds no
    ``server.bsp.*`` span, and the program has the instruments."""
    run = _made(_with(), _with(gets=40, adds=40, rounds=10,
                               round_s=(10, 0.2)))
    assert bsp_get_hold_ms_mean.read(run) == 0.0
    assert bsp_drain_ms_mean.read(run) == 0.0
    assert bsp_cached_gets_pct.read(run) == 0.0
    assert bsp_cached_adds_pct.read(run) == 0.0


@pytest.mark.parametrize("before,after", [
    ({}, {}),                            # the parent: no such instrument
    ({}, {"server.window.verbs": _counter(8)}),
    (_with(gets=4, adds=4, rounds=1, round_s=(1, 0.1)),
     _with(gets=4, adds=4, rounds=1, round_s=(1, 0.1))),     # no round
])
def test_the_readers_find_nothing_without_their_instruments(before, after):
    run = _made(before, after)
    for reader in READERS:
        assert reader.read(run) is None, reader.__name__


def test_the_span_readers_need_a_trace():
    run = _made(_with(), _with(gets=40, adds=40, rounds=10,
                               round_s=(10, 0.2)), traced=False)
    assert bsp_get_hold_ms_mean.read(run) is None
    assert bsp_drain_ms_mean.read(run) is None
    assert bsp_round_ms_mean.read(run) == pytest.approx(20.0)
