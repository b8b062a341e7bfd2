"""The six readers of the table layer's device crossings (PR 35) on a
hand-made neutral form and counter pair: the hand-computed value where the
program records the spans and counters, nothing where it does not (the
parent of PR 35 under these files)."""

import pytest

from benchmark.harness import cells, crossings
from benchmark.harness.run_record import Run
from benchmark.layer_metrics import (device_calls_per_op, h2d_mb_per_op,
                                     table_call_pct, table_place_pct,
                                     table_take_pct, table_wait_pct)

READERS = {"device_calls_per_op": device_calls_per_op,
           "h2d_mb_per_op": h2d_mb_per_op,
           "table_place_pct": table_place_pct,
           "table_call_pct": table_call_pct,
           "table_wait_pct": table_wait_pct,
           "table_take_pct": table_take_pct}
FIVE = ["mt_host_verbs", "tables_rounds_4c", "lm_vocab_steps",
        "mt_sparse_rounds", "rec_bag_steps"]


def hand_made(crossings_recorded: bool = True) -> dict:
    """Window 1000..2000 ns. The engine's thread: a Get's dispatch
    1000..1200 holding .place 1000..1040, .call 1040..1100 and .call
    1100..1150; finalize 1200..1400 holding .wait 1200..1300 and .take
    1300..1380; a sparse read 1900..2100 (crossing the window's end)
    holding .place 1900..1950, .call 1950..2050 and .take 2050..2100
    (outside). A client's thread: a device apply's dispatch 1500..1800
    holding .place 1500..1600 and .call 1600..1800, and the caller's
    ``worker.wait`` 1000..1400, which is no crossing."""
    host = [
        ["server.table.get.dispatch", 1000, 200, "engine"],
        ["server.window.finalize", 1200, 200, "engine"],
        ["server.table.sparse.get.read", 1900, 200, "engine"],
        ["server.table.device_apply.dispatch", 1500, 300, "client"],
        ["worker.wait", 1000, 400, "client"],
        ["bench.window", 1000, 1000, "client"],
    ]
    if crossings_recorded:
        host += [
            ["server.table.get.dispatch.place", 1000, 40, "engine"],
            ["server.table.get.dispatch.call", 1040, 60, "engine"],
            ["server.table.get.dispatch.call", 1100, 50, "engine"],
            ["server.window.finalize.wait", 1200, 100, "engine"],
            ["server.window.finalize.take", 1300, 80, "engine"],
            ["server.table.sparse.get.read.place", 1900, 50, "engine"],
            ["server.table.sparse.get.read.call", 1950, 100, "engine"],
            ["server.table.sparse.get.read.take", 2050, 50, "engine"],
            ["server.table.device_apply.dispatch.place", 1500, 100,
             "client"],
            ["server.table.device_apply.dispatch.call", 1600, 200,
             "client"],
        ]
    return {"devices": [], "host": sorted(host, key=lambda e: e[1]),
            "window": [1000, 2000]}


def _counter(value) -> dict:
    return {"type": "counter", "value": float(value)}


def _run(crossings_recorded: bool = True, traced: bool = True) -> Run:
    before = {"table.device_fetch.rows": _counter(10)}
    after = {"table.device_fetch.rows": _counter(50)}
    if crossings_recorded:
        before.update({"table.device.calls": _counter(100),
                       "table.device.h2d_bytes": _counter(1_000_000)})
        after.update({"table.device.calls": _counter(400),
                      "table.device.h2d_bytes": _counter(13_000_000),
                      "table.device.d2h_bytes": _counter(5)})
    return Run(cell=None, seed=0, seconds=1.0, traced=traced,
               rehearsal=False,
               trace=hand_made(crossings_recorded) if traced else None,
               window={"attempted": 4, "failed": 0},
               counters_before=before, counters_after=after)


#: by hand, of the window's 1000 ns: .place 40 + 50 + 100; .call 60 + 50
#: + 50 (1950..2000 of the read's) + 200; .wait 100; .take 80 (the
#: read's lies outside); 300 calls and 12 MB over 4 operations
EXPECTED = {"device_calls_per_op": 75.0, "h2d_mb_per_op": 3.0,
            "table_place_pct": 19.0, "table_call_pct": 36.0,
            "table_wait_pct": 10.0, "table_take_pct": 8.0}


@pytest.mark.parametrize("name", list(READERS))
def test_reader_gives_the_hand_computed_value(name):
    assert READERS[name].read(_run()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", list(READERS))
def test_a_program_without_the_names_reads_as_nothing(name):
    """The parent of PR 35: the verbs' spans and the older counters are
    there, the crossings are not."""
    assert READERS[name].read(_run(crossings_recorded=False)) is None


@pytest.mark.parametrize("name", [n for n in READERS if n.endswith("_pct")])
def test_a_span_reader_reads_nothing_from_an_untraced_run(name):
    assert READERS[name].read(_run(traced=False)) is None


def test_counter_readers_need_operations():
    run = _run()
    run.window = {"attempted": 0, "failed": 0}
    assert device_calls_per_op.read(run) is None
    assert h2d_mb_per_op.read(run) is None


def test_the_callers_wait_is_no_crossing():
    tr = hand_made()
    tr["host"] = [e for e in tr["host"] if not e[0].endswith(".wait")
                  or e[0] == "worker.wait"]
    assert crossings.share_pct(tr, ".wait") is None


def test_the_six_entries():
    bench = cells.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-6:] == list(READERS)
    for name in READERS:
        m = entries[name]
        assert (m["layer"], m["moves"], m["better"]) == (
            "row ops and kernels", "table_rows_per_s", "lower")
        assert m["source"] == ("program_counter" if name.endswith("_per_op")
                               else "program_span")
        assert m["workloads"] == (
            ["mt_host_verbs", "mt_sparse_rounds"]
            if name in ("table_wait_pct", "table_take_pct") else FIVE)
