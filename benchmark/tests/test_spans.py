"""The span arithmetic of ``harness/spans.py`` and the readers built on
it: exact on a hand-made trace with nested and cross-thread spans, and
held to a record on a trace re-recorded on the chip with the program's
own spans (``data/*.spans.json``, by ``tools/span_table.py``)."""

import glob
import json
import os

import pytest

from benchmark.harness import spans
from benchmark.harness.run_record import Run
from benchmark.layer_metrics import (block_host_ms, loader_wait_pct,
                                     prepare_host_s, round_dispatch_pct,
                                     round_prepare_pct, tables_create_s,
                                     verb_queue_wait_ms_mean,
                                     window_dispatch_ms_mean,
                                     window_finalize_ms_mean,
                                     window_merge_ms_mean)
from benchmark.tools import span_table

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SPAN_READERS = (loader_wait_pct, block_host_ms, window_finalize_ms_mean,
                window_merge_ms_mean, window_dispatch_ms_mean,
                round_prepare_pct, round_dispatch_pct)
COUNTER_READERS = (verb_queue_wait_ms_mean, prepare_host_s, tables_create_s)


def hand_made() -> dict:
    """Window 1000..2000 ns, three threads.

    engine: admit 900..1000 (outside), window A 1000..1400 holding form
    (1000..1050), merge (1050..1150), dispatch (1150..1250), get.prepare
    (1250..1270), get.dispatch (1270..1300), finalize (1300..1400); window
    B 1900..2100 crossing the window's edge, holding finalize 1950..2100.
    loop: pop_wait 800..1200 (crossing the start), block 1200..1500 with
    fetch 1200..1300 and, on the engine's thread, nothing; block
    1600..1800; pop_wait 1800..1900.
    rounds: device_fetch 1000..1300 with prepare 1000..1100 and dispatch
    1100..1300; device_apply 1400..1900 with prepare 1400..1500 and
    dispatch 1500..1900."""
    host = [
        ["server.window.admit", 900, 100, "engine"],
        ["server.window", 1000, 400, "engine"],
        ["server.window.form", 1000, 50, "engine"],
        ["server.table.add_run.merge", 1050, 100, "engine"],
        ["server.table.add_run.dispatch", 1150, 100, "engine"],
        ["server.table.get.prepare", 1250, 20, "engine"],
        ["server.table.get.dispatch", 1270, 30, "engine"],
        ["server.window.finalize", 1300, 100, "engine"],
        ["server.window", 1900, 200, "engine"],
        ["server.window.finalize", 1950, 150, "engine"],
        ["worker.we.pop_wait", 800, 400, "loop"],
        ["worker.we.block", 1200, 300, "loop"],
        ["worker.we.fetch", 1200, 100, "loop"],
        ["worker.we.block", 1600, 200, "loop"],
        ["worker.we.pop_wait", 1800, 100, "loop"],
        ["server.table.device_fetch", 1000, 300, "rounds"],
        ["server.table.device_fetch.prepare", 1000, 100, "rounds"],
        ["server.table.device_fetch.dispatch", 1100, 200, "rounds"],
        ["server.table.device_apply", 1400, 500, "rounds"],
        ["server.table.device_apply.prepare", 1400, 100, "rounds"],
        ["server.table.device_apply.dispatch", 1500, 400, "rounds"],
        ["bench.window", 1000, 1000, "loop"],
    ]
    return {"devices": [], "host": sorted(host, key=lambda e: e[1]),
            "window": [1000, 2000]}


def _run(tr, **kw) -> Run:
    return Run(cell=None, seed=0, seconds=1.0, traced=True, rehearsal=False,
               trace=tr, **kw)


def test_totals_and_counts_clip_at_the_window():
    tr = hand_made()
    assert spans.window_s(tr) == pytest.approx(1000e-9)
    # 800..1200 counts for 1000..1200; 1800..1900 whole
    assert spans.total_s(tr, "worker.we.pop_wait") == pytest.approx(300e-9)
    assert spans.count(tr, "worker.we.pop_wait") == 2
    # 1900..2100 counts for 1900..2000
    assert spans.total_s(tr, "server.window") == pytest.approx(500e-9)
    assert spans.count(tr, "server.window") == 2
    # wholly outside: named in the trace, nothing inside the window
    assert spans.count(tr, "server.window.admit") == 0
    assert spans.total_s(tr, "server.window.admit") == 0.0
    assert spans.mean_ms(tr, "server.window.admit") is None
    assert spans.mean_ms(tr, "worker.we.block") == pytest.approx(250e-6)
    # several names add up
    assert spans.total_s(tr, "server.table.add_run.merge",
                         "server.table.get.prepare") == pytest.approx(120e-9)


def test_absent_names_give_none():
    tr = hand_made()
    for f in (spans.count, spans.total_s, spans.mean_ms, spans.self_s,
              spans.share_pct):
        assert f(tr, "worker.we.harvest") is None
    assert spans.per_ms(tr, "server.window", "no.such.span") is None
    assert spans.per_ms(tr, "no.such.span", "server.window.form") is None


def test_self_time_is_by_nesting_on_the_span_s_own_thread():
    tr = hand_made()
    # window A: 400 less 50+100+100+20+30+100 = 0; window B inside the
    # window: 100 less finalize's 50 = 50
    assert spans.self_s(tr, "server.window") == pytest.approx(50e-9)
    # fetch (loop thread) comes off the first block; the engine's spans
    # at the same instants do not
    assert spans.self_s(tr, "worker.we.block") == pytest.approx(400e-9)
    assert spans.self_s(tr, "server.table.device_apply") == pytest.approx(0)
    # a leaf owns all of itself
    assert spans.self_s(tr, "server.window.form") == pytest.approx(50e-9)
    # bench.window on the loop's thread holds the blocks (500) and the
    # second pop_wait (100); the first began before it, so does not nest
    # in it, and the other threads take nothing
    assert spans.self_s(tr, "bench.window") == pytest.approx(400e-9)


def test_readers_on_the_hand_made_trace():
    run = _run(hand_made())
    assert loader_wait_pct.read(run) == pytest.approx(30.0)
    assert block_host_ms.read(run) == pytest.approx(250e-6)
    # two windows reach into the window; finalize 100 + 50
    assert window_finalize_ms_mean.read(run) == pytest.approx(75e-6)
    assert window_merge_ms_mean.read(run) == pytest.approx(60e-6)
    assert window_dispatch_ms_mean.read(run) == pytest.approx(65e-6)
    assert round_prepare_pct.read(run) == pytest.approx(20.0)
    assert round_dispatch_pct.read(run) == pytest.approx(60.0)
    assert span_table.read_all(hand_made()) == pytest.approx({
        "loader_wait_pct": 30.0, "block_host_ms": 250e-6,
        "window_finalize_ms_mean": 75e-6, "window_merge_ms_mean": 60e-6,
        "window_dispatch_ms_mean": 65e-6, "round_prepare_pct": 20.0,
        "round_dispatch_pct": 60.0})


@pytest.mark.parametrize("reader", SPAN_READERS + COUNTER_READERS,
                         ids=lambda r: r.__name__.rsplit(".", 1)[-1])
def test_a_program_without_the_instrument_reads_as_nothing(reader):
    """The parent commit has none of these spans or counters: each reader
    returns None there and does not raise, with and without a trace."""
    bare = {"devices": [], "host": [["bench.window", 0, 10, "main"],
                                    ["worker.get", 1, 2, "main"]],
            "window": [0, 10]}
    old_counters = {"server.window.verbs": {"type": "counter", "value": 3.0}}
    for tr in (bare, None):
        run = _run(tr, counters_before=old_counters,
                   counters_after=old_counters)
        assert reader.read(run) is None


def test_counter_readers():
    before = {"actor.server.queue_wait_s": {"count": 10, "sum": 0.010},
              "actor.worker.queue_wait_s": {"count": 1, "sum": 5.0}}
    after = {"actor.server.queue_wait_s": {"count": 30, "sum": 0.050},
             "actor.server_shard1.queue_wait_s": {"count": 20, "sum": 0.020},
             "actor.worker.queue_wait_s": {"count": 9, "sum": 50.0},
             "we.prepare.dictionary_s": {"type": "gauge", "value": 5.5},
             "we.prepare.sampler_s": {"type": "gauge", "value": 0.5},
             "we.prepare.tables_s": {"type": "gauge", "value": 9.0},
             "table.create_s": {"count": 4, "sum": 8.0}}
    run = _run(None, counters_before=before, counters_after=after)
    # (0.040 + 0.020) s over 20 + 20 verbs; the worker actor's is not read
    assert verb_queue_wait_ms_mean.read(run) == pytest.approx(1.5)
    # absolute values after the window: set-up ends before the first
    # snapshot, so a difference would be 0
    assert prepare_host_s.read(run) == pytest.approx(6.0)
    assert tables_create_s.read(run) == pytest.approx(8.0)
    same = _run(None, counters_before=after, counters_after=after)
    assert verb_queue_wait_ms_mean.read(same) is None
    assert prepare_host_s.read(same) == pytest.approx(6.0)


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(DATA, "*.spans.json"))) or [None])
def test_recorded_chip_traces_hold_the_program_s_spans(path):
    if path is None:
        pytest.skip("no recorded span figures under tests/data")
    with open(path) as f:
        expected = json.load(f)
    with open(path.replace(".spans.json", ".trace.json")) as f:
        tr = json.load(f)
    assert expected, "the recording holds none of the program's spans"
    assert span_table.read_all(tr) == pytest.approx(expected)
    names = {e[0] for e in tr["host"]}
    assert any(n.startswith(("worker.we.", "server.window.",
                             "server.table.")) for n in names)
    for n in names:
        assert 0.0 <= spans.self_s(tr, n) <= spans.total_s(tr, n) + 1e-12
