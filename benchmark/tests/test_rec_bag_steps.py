"""The cell ``rec_bag_steps``: its rehearsal ends ``correct`` with the
contract's last line and compiles nothing in its window; its id law and
its warm-up's reckoning; its three readers on a hand-made run; and its
tolerance, which refuses the replay kept in bfloat16, a dropped step,
repeats left unsummed and a neighbouring table's delta."""

import json

import ml_dtypes
import numpy as np
import pytest

from benchmark.harness import cells
from benchmark.harness.run_record import Run
from benchmark.layer_metrics import (apply_combine_ms_per_step,
                                     apply_pallas_verb_pct,
                                     device_verb_host_ms_mean)
from benchmark.reference import adagrad_rows
from benchmark.runners import table_bag_steps
from benchmark.tests.test_last_line import _run

CELL = cells.load_cell("rec_bag_steps")
LR, RHO = CELL.traffic["learning_rate"], CELL.traffic["rho"]


def test_rehearsal_ends_correct_with_the_contract_line():
    res = _run("--workload", "rec_bag_steps", "--seed", str(2**31 + 33),
               "--seconds", "1", "--trace", "1", "--rehearsal")
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "rehearsal"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1 and line["device"]["platform"] == "cpu"
    allowed = {m["name"]: m["unit"] for m in CELL.per_layer}
    assert set(line["metrics"]) <= set(allowed)
    for name in ("apply_combine_ms_per_step", "device_verb_host_ms_mean",
                 "apply_pallas_verb_pct"):
        assert line["metrics"][name]["unit"] == allowed[name]
    assert line["metrics"]["apply_d2h_mb_per_step"]["value"] == 0.0
    assert line["metrics"]["tables_window_compiles"]["value"] == 0.0
    # no kernel on a CPU, and a share of a chip's peak is never read off one
    assert line["metrics"]["apply_pallas_verb_pct"]["value"] == 0.0
    assert "row_plane_roofline" not in line["metrics"]


def test_the_configuration_states_its_source_and_its_share():
    cfg, pub = CELL.config, CELL.config["published"]
    assert sum(pub["num_embeddings_per_feature"]) \
        == pub["num_embeddings_sum"] == 204_184_588
    assert sum(pub["multi_hot_sizes"]) == pub["multi_hot_sizes_sum"] == 214
    assert pub["ids_per_step"] == 65_536 * 214 == 14_024_704
    held = [-(-n // cfg["servers"])
            for n in pub["num_embeddings_per_feature"]]
    assert held == cfg["rows"] and sum(held) == cfg["rows_sum"] == 6_380_781
    assert cfg["cols"] == pub["embedding_dim"] == 128
    positions = [CELL.traffic["bags"] * h for h in pub["multi_hot_sizes"]]
    assert CELL.traffic["bags"] * cfg["servers"] == pub["batch_size"]
    assert sum(positions) == 438_272 and max(positions) == 204_800


def test_bags_name_every_row_of_a_tiny_table_and_skew_the_first_id():
    rng = np.random.default_rng(3)
    for rows in (1, 2, 5):
        ids = table_bag_steps.bag_ids(rng, rows, 2048, 1, np.arange(rows))
        assert sorted(set(ids.tolist())) == list(range(rows))
    ids = table_bag_steps.bag_ids(rng, 1000, 4096, 3, np.arange(1000))
    ids = ids.reshape(4096, 3)
    assert ids.min() >= 0 and ids.max() < 1000 and ids.dtype == np.int32
    # rank 1 of 1,000: log(2) / log(1001) of the first ids, a tenth;
    # a thousandth of the uniform ones
    assert 0.07 < np.mean(ids[:, 0] == 0) < 0.13
    assert np.mean(ids[:, 1:] == 0) < 0.004


def test_the_warm_up_covers_every_class_a_table_meets():
    a, b, c = (False, 3), (True, 3), (True, 4)
    classes = [[b, b], [b, c], [a, b], [b, b]]
    picked = table_bag_steps.covering_sets(classes)
    assert len(picked) <= 3 and len(set(picked)) == len(picked)
    met = {(t, k) for s in picked for t, k in enumerate(classes[s])}
    assert met == {(t, k) for row in classes for t, k in enumerate(row)}
    assert table_bag_steps.distinct_class(3, 2) == (True, 1)
    assert table_bag_steps.distinct_class(9, 9) == (False, 4)
    # the deployment's 26 tables: 13 of up to 267 rows whole, 267 of the rest
    assert table_bag_steps.sample_quota(CELL.config["rows"], 4096) == 267
    assert table_bag_steps.sample_quota([3, 5], 4096) == 4093


def _hand_made_run():
    """Two steps of two tables: four fetches of 100 us, four applies of
    300 us, a combine of 50 us inside three of the applies."""
    host, at = [["bench.window", 0, 4000, "main"]], 0
    for step in range(2):
        host.append(["bench.step", at, 2000, "main"])
        for table in range(2):
            host.append(["server.table.device_fetch", at, 100, "main"])
            host.append(["server.table.device_apply", at + 200, 300, "main"])
            if (step, table) != (1, 1):
                host.append(["server.table.device_apply.combine", at + 210,
                             50, "main"])
            at += 1000
    run = Run(cell=None, seed=0, seconds=1.0, traced=True, rehearsal=False,
              trace={"devices": [], "host": host, "window": [0, 4000]})
    run.counters_before = {
        "table.device_apply.pallas_verbs": {"value": 10.0}}
    run.counters_after = {
        "table.device_apply.pallas_verbs": {"value": 11.0},
        "table.device_apply.xla_verbs": {"value": 1.0},
        "table.device_apply.small_table_verbs": {"value": 2.0}}
    return run


def test_the_three_readers_on_a_hand_made_run():
    run = _hand_made_run()
    assert apply_combine_ms_per_step.read(run) == pytest.approx(75e-6)
    assert device_verb_host_ms_mean.read(run) == pytest.approx(200e-6)
    assert apply_pallas_verb_pct.read(run) == pytest.approx(25.0)


def test_the_readers_find_nothing_in_a_program_without_their_sources():
    """The parent commit has the spans and not the counters; a run that
    was not traced has neither."""
    run = _hand_made_run()
    run.counters_before = run.counters_after = {
        "table.device_apply.rows": {"value": 5.0}}
    assert apply_pallas_verb_pct.read(run) is None
    run.trace = None
    assert apply_combine_ms_per_step.read(run) is None
    assert device_verb_host_ms_mean.read(run) is None


# -- the tolerance -----------------------------------------------------------

STEPS = 96      # what a 10 s window and its warm-up make, about


def _tables():
    """(table number, initial rows, counts a step) of three kinds of the
    deployment's tables: a one-row table every position of which names
    row 0 (2,048 repeats in every step); a table of a few hundred rows
    whose rows are named a few times a step, the number changing; a large
    table whose rows are named now and then."""
    rng = np.random.default_rng(33)
    pub = CELL.config["published"]["num_embeddings_per_feature"]

    def init(table, rows):
        return ((2 * rng.random((rows, 128), dtype=np.float32) - 1)
                * np.float32(1 / np.sqrt(pub[table])))
    return [(5, init(5, 1), [np.array([2048])] * STEPS),
            (2, init(2, 64), [rng.poisson(3.8, 64) for _ in range(STEPS)]),
            (0, init(0, 64), [rng.poisson(0.05, 64) for _ in range(STEPS)])]


def _refused(err) -> bool:
    """The runner's verdict on a table's errors, by either limit."""
    tol = CELL.workload["tolerance"]
    return bool(err.max() > tol["worst_abs"]
                or np.mean(err <= tol["entry_abs"]) < tol["entry_share"])


def _replay(table, init, counts, **kw):
    return adagrad_rows.replay(init, counts, table, learning_rate=LR,
                               rho=RHO, **kw)[0].astype(np.float64)


@pytest.mark.parametrize("kind", range(3))
def test_the_tolerance_refuses_bfloat16_a_dropped_step_and_a_neighbour(kind):
    table, init, counts = _tables()[kind]
    exact = _replay(table, init, counts)
    assert not _refused(np.abs(_replay(table, init, counts) - exact))
    # rows and history kept in bfloat16, the precision below the stated one
    assert _refused(np.abs(_replay(table, init, counts,
                                   store=ml_dtypes.bfloat16) - exact))
    # the last step that names a row left out
    last = max(i for i, c in enumerate(counts) if c.any())
    assert _refused(np.abs(
        _replay(table, init, counts[:last] + counts[last + 1:]) - exact))
    # the delta made for the next table of the step applied to this one
    assert _refused(np.abs(_replay(table + 1, init, counts) - exact))


def test_the_tolerance_refuses_repeats_left_unsummed():
    """On a table whose counts change from step to step: AdaGrad's step is
    the same for every constant multiple of a gradient, so the one-row
    tables (2,048 repeats in every step) cannot show it, and the sample
    holds the most frequent rows of the larger tables for this."""
    table, init, counts = _tables()[1]
    exact = _replay(table, init, counts)
    once = _replay(table, init, [np.minimum(c, 1) for c in counts])
    assert _refused(np.abs(once - exact))
    table, init, counts = _tables()[0]      # all but hidden where it is fixed
    fixed = _replay(table, init, [np.minimum(c, 1) for c in counts])
    fixed = np.abs(fixed - _replay(table, init, counts))
    assert 100 * fixed.max() < np.abs(once - exact).max()
