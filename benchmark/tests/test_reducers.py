"""The reduction from trace to numbers: exact on a hand-made trace, and
sane on the traces recorded on the chip under ``data/``."""

import glob
import json
import os

import pytest

from benchmark.harness import trace
from benchmark.harness.run_record import Run
from benchmark.layer_metrics import (collective_busy_pct,
                                     custom_call_busy_pct, device_idle_pct,
                                     top_op_busy_pct)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def hand_made() -> dict:
    """Window 0..1000 ns. A ``while`` of 600 ns holds a fusion (200) and a
    custom call (300); then an all-reduce (100) alone; 300 ns idle."""
    ops = [["while.1", 0, 600, "other"],
           ["fusion.2", 50, 200, "other"],
           ["scatter_kernel", 250, 300, "custom-call"],
           ["all-reduce.3", 700, 100, "collective"]]
    host = [["bench.window", 0, 1000, "main"],
            ["bench.round", 0, 650, "main"],
            ["bench.round", 650, 350, "main"],
            ["worker.get", 900, 50, "main"]]
    return {"devices": [{"name": "/device:TPU:0", "line": "XLA Ops",
                         "ops": ops}],
            "host": host, "window": [0, 1000]}


def _run(tr) -> Run:
    return Run(cell=None, seed=0, seconds=1.0, traced=True, rehearsal=False,
               trace=tr)


def test_busy_is_the_union_and_own_time_excludes_children():
    tr = hand_made()
    s = trace.summary(tr)
    dev = s["devices"][0]
    assert s["window_s"] == pytest.approx(1000e-9)
    assert dev["busy_s"] == pytest.approx(700e-9)
    assert dev["by_name_s"] == pytest.approx(
        {"while.1": 100e-9, "fusion.2": 200e-9, "scatter_kernel": 300e-9,
         "all-reduce.3": 100e-9})
    assert sum(dev["by_name_s"].values()) == pytest.approx(dev["busy_s"])


def test_readers_on_the_hand_made_trace():
    run = _run(hand_made())
    assert device_idle_pct.read(run) == pytest.approx(30.0)
    assert custom_call_busy_pct.read(run) == pytest.approx(100 * 3 / 7)
    assert collective_busy_pct.read(run) == pytest.approx(100 * 1 / 7)
    assert top_op_busy_pct.read(run) == pytest.approx(100 * 3 / 7)


def test_idle_goes_to_the_innermost_covering_span():
    gaps = trace.idle_by_span(hand_made())
    # 600..700 lies in the second bench.round (midpoint 650 is covered by
    # both rounds' edges; the shorter wins), 800..1000 holds worker.get
    assert gaps == pytest.approx({"bench.round": 100e-9,
                                  "worker.get": 200e-9})
    b = trace.breakdown(hand_made(), trace.summary(hand_made()))
    assert b["device_ops"][0] == ["scatter_kernel", pytest.approx(300e-9)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_window_clips_operations():
    tr = hand_made()
    tr["window"] = [100, 500]
    dev = trace.summary(tr)["devices"][0]
    assert dev["busy_s"] == pytest.approx(400e-9)


def test_a_reader_with_nothing_to_read_returns_nothing():
    run = Run(cell=None, seed=0, seconds=1.0, traced=False, rehearsal=False)
    for reader in (device_idle_pct, custom_call_busy_pct,
                   collective_busy_pct, top_op_busy_pct):
        assert reader.read(run) is None


def test_categories():
    assert trace.category("all-reduce.7", {}) == "collective"
    assert trace.category("%all-gather-start.1", {}) == "collective"
    assert trace.category("fusion.3", {"hlo_category": "custom-call"}) \
        == "custom-call"
    assert trace.category("fusion.3", {}) == "other"
    line = ("%pallas_scatter_set_rows.1 = f32[9000001,128]{1,0:T(8,128)} "
            "custom-call(s32[65536]{0} %a, f32[65536,128]{1,0} %b), "
            "custom_call_target=\"tpu_custom_call\"")
    assert trace.short_name(line) == "pallas_scatter_set_rows.1"
    assert trace.category(line, {}) == "custom-call"
    line = "%psum.7 = f32[65536,128]{1,0} all-reduce(f32[65536,128] %x)"
    assert trace.short_name(line) == "psum.7"
    assert trace.category(line, {}) == "collective"


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(DATA, "*.trace.json"))) or [None])
def test_recorded_chip_traces_reduce(path):
    if path is None:
        pytest.skip("no recorded trace under tests/data")
    with open(path) as f:
        tr = json.load(f)
    run = _run(tr)
    s = run.trace_summary()
    assert s["devices"] and s["window_s"] > 0
    for dev in s["devices"]:
        assert 0 < dev["busy_s"] <= s["window_s"]
        assert sum(dev["by_name_s"].values()) == pytest.approx(
            dev["busy_s"], rel=1e-6)
    assert 0 <= device_idle_pct.read(run) < 100
    assert 0 <= custom_call_busy_pct.read(run) <= 100
    assert 0 < top_op_busy_pct.read(run) <= 100
    with open(path.replace(".trace.json", ".expected.json")) as f:
        expected = json.load(f)
    assert device_idle_pct.read(run) == pytest.approx(
        expected["device_idle_pct"])
    assert custom_call_busy_pct.read(run) == pytest.approx(
        expected["custom_call_busy_pct"])
    assert collective_busy_pct.read(run) == pytest.approx(
        expected["collective_busy_pct"])
    assert trace.breakdown(tr, s)["device_ops"][0][0] == expected["top_op"]
