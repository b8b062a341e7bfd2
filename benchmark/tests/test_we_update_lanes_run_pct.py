"""The per-layer metric ``we_update_lanes_run_pct``: its entry in
``BENCHMARK.json`` (the two one-chip cells whose step is ``-device_pairs``'
touched-rows one, found by name and not by position), and its reader on
hand-made runs: the share of the laid-out update lanes that ran, nothing
without the program's two counters (the parent's side of a pair) and
nothing in a window that laid out no lane."""

import pytest

from benchmark.harness import cells
from benchmark.harness.run_record import Run
from benchmark.layer_metrics import we_update_lanes_run_pct

NAME = "we_update_lanes_run_pct"
CELLS = ["we_pairs", "we_cbow_hs"]


def test_the_entry_and_its_two_cells():
    bench = cells.load_benchmark()
    entries = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entries == [{"name": NAME, "unit": "%", "better": "lower",
                        "source": "program_counter",
                        "layer": "updaters and fused steps",
                        "moves": "train_items_per_s", "workloads": CELLS}]
    e2e = {m["name"]: m["workloads"] for m in bench["end_to_end"]
           if "workloads" in m}
    assert set(CELLS) <= set(e2e[entries[0]["moves"]])
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] != NAME}
    assert entries[0]["layer"] in layers
    for w in bench["workloads"]:
        reported = {m["name"] for m in cells.load_cell(w["name"]).per_layer}
        assert (NAME in reported) == (w["name"] in CELLS), w["name"]
    assert cells.load_reader("layer_metrics", NAME)


def _run(before, after):
    run = Run(cell=None, seed=0, seconds=1.0, traced=True, rehearsal=False)
    as_counters = lambda d: {  # noqa: E731
        "we.update.lanes." + k: {"type": "counter", "value": float(v)}
        for k, v in d.items()}
    run.counters_before = as_counters(before)
    run.counters_after = as_counters(after)
    return run


CHUNK = 8_192                       # one batch's pairs
CBOW_HS = 81_920 + 221_184          # a CBOW + HS step's lanes, 37 chunks
SKIPGRAM = 8_192 + 49_152           # a skip-gram step's, 7 chunks


@pytest.mark.parametrize("before,after,want", [
    # a CBOW + HS pass of 49 steps, seven chunks of input rows and four of
    # inner nodes a step; counters new in the window
    ({}, {"run": 49 * 11 * CHUNK, "laid_out": 49 * CBOW_HS},
     100 * 11 / 37),
    # a skip-gram pass after a warm-up pass: the input update's one chunk
    # and five of the output update's six
    ({"run": 488 * 6 * CHUNK, "laid_out": 488 * SKIPGRAM},
     {"run": 976 * 6 * CHUNK, "laid_out": 976 * SKIPGRAM}, 100 * 6 / 7),
    # every lane ran (the per-shard step of more than one chip)
    ({"run": 5, "laid_out": 5}, {"run": 5 + 3 * SKIPGRAM,
                                 "laid_out": 5 + 3 * SKIPGRAM}, 100.0),
])
def test_the_reader_on_hand_made_runs(before, after, want):
    assert we_update_lanes_run_pct.read(_run(before, after)) == \
        pytest.approx(want)


@pytest.mark.parametrize("before,after", [
    ({}, {}),                                            # the parent
    ({}, {"laid_out": CBOW_HS}),                         # half of the pair
    ({}, {"run": CBOW_HS}),
    ({"run": 4, "laid_out": 8}, {"run": 4, "laid_out": 8}),     # no block
    ({}, {"run": 0, "laid_out": 0}),                     # the dense step
])
def test_the_reader_finds_nothing_without_its_counters(before, after):
    run = _run(before, after)
    run.counters_after["we.blocks"] = {"type": "counter", "value": 3.0}
    assert we_update_lanes_run_pct.read(run) is None
