"""The ten entry-point metrics of the start-up ledger (PR 52): their
entries and files, asked for by name and by membership (never by position:
a later PR appends); each reader on a hand-made run whose snapshots hold
the program's instruments, to the digit, and on one whose snapshots lack
them (the parent's side of a pair: nothing, and no error); a traced
rehearsal of one WordEmbedding cell and one table cell prints the ones
that apply to it."""

import json

import pytest

from benchmark.harness import cells
from benchmark.harness.run_record import Run
from benchmark.tests.test_last_line import _run
from benchmark.tools import setup_table

WE = ["we_pairs", "we_rows", "we_pairs_4c", "we_cbow_hs", "we_host_pipeline"]
TABLES = ["mt_host_verbs", "tables_rounds_4c", "lm_vocab_steps",
          "mt_sparse_rounds", "rec_bag_steps", "mt_bsp_rounds"]
SETUP = {"setup_import_s": "s", "setup_init_s": "s",
         "setup_jit_trace_s": "s", "setup_jit_lower_s": "s",
         "setup_jit_backend_s": "s", "setup_cache_load_s": "s",
         "setup_programs": "count", "setup_unaccounted_s": "s"}
WINDOW = {"window_jit_ms": ("train_items_per_s", WE),
          "tables_window_jit_ms": ("table_rows_per_s", TABLES)}


def _hist(count, total):
    return {"type": "histogram", "count": count, "sum": total}


def _value(kind, value):
    return {"type": kind, "value": value}


BEFORE = {"mv.import_s": _value("gauge", 3.25),
          "mv.init_s": _value("gauge", 0.5),
          "startup.phased_s": _value("counter", 4.0),
          "jit.unphased_s": _value("counter", 1.5),
          "jit.trace_s": _hist(7, 0.75), "jit.lower_s": _hist(7, 2.0),
          "jit.backend_s": _hist(6, 1.25),
          "jit.cache_load_s": _hist(5, 0.625),
          "jit.program.step.seconds": _value("counter", 3.0),
          "jit.program.step.builds": _value("counter", 4.0),
          "jit.program.step.cache_hits": _value("counter", 4.0),
          "jit.program.pad.seconds": _value("counter", 1.0),
          "jit.program.pad.builds": _value("counter", 2.0)}
AFTER = {**BEFORE,
         "mv.import_s": _value("gauge", 3.5),       # a late lazy import
         "jit.trace_s": _hist(8, 0.875), "jit.lower_s": _hist(8, 2.25),
         "jit.backend_s": _hist(7, 1.75),
         "jit.program.pad.seconds": _value("counter", 1.875),
         "jit.program.pad.builds": _value("counter", 3.0)}


def _made(before, after, setup_s=10.0):
    return Run(cell=None, seed=0, seconds=0.0, traced=True, rehearsal=True,
               setup_s=setup_s, counters_before=before, counters_after=after)


def _read(name, run):
    return cells.load_reader("layer_metrics", name)(run)


def test_the_ten_entries_by_name():
    bench = cells.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, unit in SETUP.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": "lower",
            "source": "program_counter", "layer": "entry points",
            "moves": "setup_s"}, name
    for name, (moves, where) in WINDOW.items():
        assert by_name[name] == {
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_counter", "layer": "entry points",
            "moves": moves, "workloads": where}, name
    for name in list(SETUP) + list(WINDOW):
        assert cells.load_reader("layer_metrics", name)
    # the counts from outside stay beside them
    for name in ("setup_compiled_programs", "window_compiles",
                 "tables_window_compiles"):
        assert name in by_name


@pytest.mark.parametrize("cell", WE + TABLES)
def test_every_cell_reports_the_eight_and_its_own_window_metric(cell):
    reported = {m["name"] for m in cells.load_cell(cell).per_layer}
    assert set(SETUP) <= reported
    assert ("window_jit_ms" in reported) == (cell in WE)
    assert ("tables_window_jit_ms" in reported) == (cell in TABLES)


@pytest.mark.parametrize("name,value", [
    ("setup_import_s", 3.25),           # the snapshot at set-up's end
    ("setup_init_s", 0.5),
    ("setup_jit_trace_s", 0.75),
    ("setup_jit_lower_s", 2.0),
    ("setup_jit_backend_s", 1.25),
    ("setup_cache_load_s", 0.625),
    ("setup_programs", 6),
    ("setup_unaccounted_s", 10.0 - 4.0 - 1.5),
    ("window_jit_ms", 1e3 * (0.125 + 0.25 + 0.5)),
    ("tables_window_jit_ms", 1e3 * (0.125 + 0.25 + 0.5)),
])
def test_a_reader_on_a_hand_made_run(name, value):
    assert _read(name, _made(BEFORE, AFTER)) == value


@pytest.mark.parametrize("name", list(SETUP) + list(WINDOW))
@pytest.mark.parametrize("before,after", [
    ({}, {}),
    ({"table.create_s": _hist(1, 0.1)},
     {"table.create_s": _hist(1, 0.1),
      "server.window.verbs": _value("counter", 9.0)})])
def test_a_program_without_the_ledger_reads_as_nothing(name, before, after):
    assert _read(name, _made(before, after)) is None


def test_a_cold_run_loads_nothing_and_reads_zero_not_nothing():
    cold = {k: v for k, v in BEFORE.items() if k != "jit.cache_load_s"}
    assert _read("setup_cache_load_s", _made(cold, cold)) == 0.0
    # and a window that builds nothing reads 0
    assert _read("window_jit_ms", _made(BEFORE, BEFORE)) == 0.0
    # every compile of a set-up inside a phase: no jit.unphased_s yet
    phased = {k: v for k, v in BEFORE.items() if k != "jit.unphased_s"}
    assert _read("setup_unaccounted_s", _made(phased, phased)) == 6.0


def test_set_up_adds_up_by_construction():
    run = _made(BEFORE, AFTER, setup_s=7.125)
    assert (BEFORE["startup.phased_s"]["value"]
            + BEFORE["jit.unphased_s"]["value"]
            + _read("setup_unaccounted_s", run)) == run.setup_s


def test_the_table_of_the_tool():
    t = setup_table.table(BEFORE, AFTER, setup_s=10.0)
    assert t["unaccounted_s"] == 4.5
    assert t["gauges"] == {"mv.import_s": 3.25, "mv.init_s": 0.5}
    assert t["histograms"]["jit.backend_s"] == {"count": 6, "sum": 1.25}
    assert [r["program"] for r in t["programs"]] == ["step", "pad"]
    assert t["programs"][0] == {"program": "step", "seconds": 3.0,
                                "builds": 4, "cache_hits": 4}
    assert t["window_programs"] == [{"program": "pad", "seconds": 0.875,
                                     "builds": 1, "cache_hits": 0}]
    setup_table.show(t)                 # prints, and does not raise
    bare = setup_table.table({"jit.backend_s": _hist(1, 0.5)}, {})
    assert bare["programs"] == [] and "unaccounted_s" not in bare


@pytest.mark.parametrize("cell", ["we_pairs", "mt_host_verbs"])
def test_a_traced_rehearsal_prints_the_metrics_that_apply(cell):
    res = _run("--workload", cell, "--seed", str(2**31 + 52), "--seconds",
               "1", "--trace", "1", "--rehearsal")
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    mine, other = (("window_jit_ms", "tables_window_jit_ms") if cell in WE
                   else ("tables_window_jit_ms", "window_jit_ms"))
    assert set(SETUP) | {mine} <= set(got) and other not in got
    for name, unit in SETUP.items():
        assert line["metrics"][name]["unit"] == unit
    assert got["setup_import_s"] > 0 and got["setup_init_s"] > 0
    assert got["setup_programs"] >= 1
    assert got["setup_cache_load_s"] <= got["setup_jit_backend_s"]
    assert got["setup_unaccounted_s"] >= 0
    assert got[mine] == 0.0             # the warm-up met every shape
