"""The per-layer metric ``we_steps_run_pct``: its entry in
``BENCHMARK.json`` (the three cells whose work is the block program's
loop, and no other), and its reader on hand-made runs: the share of the
laid-out steps that ran, nothing without the program's two counters
(the parent's side of a pair) and nothing in a window that laid out no
step."""

import pytest

from benchmark.harness import cells
from benchmark.harness.run_record import Run
from benchmark.layer_metrics import we_steps_run_pct

CELLS = ["we_pairs", "we_pairs_4c", "we_cbow_hs"]


def test_the_entry_and_its_three_cells():
    bench = cells.load_benchmark()
    entry = bench["per_layer"][-1]
    assert entry == {"name": "we_steps_run_pct", "unit": "%",
                     "better": "lower", "source": "program_counter",
                     "layer": "updaters and fused steps",
                     "moves": "train_items_per_s", "workloads": CELLS}
    assert [m["name"] for m in bench["per_layer"]].count(entry["name"]) == 1
    e2e = {m["name"]: m["workloads"] for m in bench["end_to_end"]
           if "workloads" in m}
    assert set(CELLS) <= set(e2e[entry["moves"]])
    for w in bench["workloads"]:
        reported = {m["name"] for m in cells.load_cell(w["name"]).per_layer}
        assert (entry["name"] in reported) == (w["name"] in CELLS), w["name"]
    assert cells.load_reader("layer_metrics", entry["name"])


def _run(before, after):
    run = Run(cell=None, seed=0, seconds=1.0, traced=True, rehearsal=False)
    as_counters = lambda d: {  # noqa: E731
        "we.block.steps." + k: {"type": "counter", "value": float(v)}
        for k, v in d.items()}
    run.counters_before = as_counters(before)
    run.counters_after = as_counters(after)
    return run


@pytest.mark.parametrize("before,after,want", [
    # a skip-gram pass after a warm-up pass: 768 laid out, 490 run
    ({"run": 490, "laid_out": 768}, {"run": 980, "laid_out": 1536},
     100 * 490 / 768),
    # a CBOW + HS pass, counters new in the window
    ({}, {"run": 49, "laid_out": 80}, 100 * 49 / 80),
    # every laid-out step ran; none did (blocks of one-word sentences)
    ({"run": 4, "laid_out": 4}, {"run": 12, "laid_out": 12}, 100.0),
    ({"run": 4, "laid_out": 4}, {"run": 4, "laid_out": 12}, 0.0),
])
def test_the_reader_on_hand_made_runs(before, after, want):
    assert we_steps_run_pct.read(_run(before, after)) == pytest.approx(want)


@pytest.mark.parametrize("before,after", [
    ({}, {}),                                            # the parent
    ({}, {"laid_out": 80}),                              # half of the pair
    ({}, {"run": 49}),
    ({"run": 49, "laid_out": 80}, {"run": 49, "laid_out": 80}),  # no block
])
def test_the_reader_finds_nothing_without_its_counters(before, after):
    run = _run(before, after)
    run.counters_after["we.blocks"] = {"type": "counter", "value": 3.0}
    assert we_steps_run_pct.read(run) is None
