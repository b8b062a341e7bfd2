"""The cell ``lm_vocab_steps``: its rehearsal ends ``correct`` with the
contract's last line, its roofline's byte function equals a hand count,
and its tolerance refuses the replay kept in bfloat16."""

import json

import ml_dtypes
import numpy as np

from benchmark.harness import cells
from benchmark.layer_metrics import row_plane_roofline
from benchmark.reference import adagrad_rows
from benchmark.tests.test_last_line import _run


def test_rehearsal_ends_correct_with_the_contract_line():
    res = _run("--workload", "lm_vocab_steps", "--seed", str(2**31 + 11),
               "--seconds", "1", "--trace", "1", "--rehearsal")
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "rehearsal"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1 and line["device"]["platform"] == "cpu"
    cell = cells.load_cell("lm_vocab_steps")
    allowed = {m["name"]: m["unit"] for m in cell.per_layer}
    assert set(line["metrics"]) <= set(allowed)
    assert line["metrics"]["apply_d2h_mb_per_step"]["value"] == 0.0
    assert line["metrics"]["tables_window_compiles"]["value"] == 0.0
    # a share of a chip's peak is never read off a CPU
    assert "row_plane_roofline" not in line["metrics"]


def test_least_bytes_on_a_four_row_table():
    """Four rows of 8 float32 (32 bytes). A fetch of positions [2, 0, 2, 2]
    reads rows 0 and 2 and writes four: 6 rows. An apply on them under
    AdaGrad reads four delta rows, and reads and writes rows 0 and 2 of the
    table and of the history: 4 + 2 * 2 * 2 = 12 rows. The whole table in
    order: fetch 4 + 4, apply 4 + 2 * 2 * 4 = 20 rows."""
    by_position = [
        {"verb": "fetch", "positions": 4, "unique": 2, "row_bytes": 32,
         "state": 2},
        {"verb": "apply", "positions": 4, "unique": 2, "row_bytes": 32,
         "state": 2}]
    whole = [dict(v, unique=4) for v in by_position]
    assert row_plane_roofline.least_bytes(by_position) == (6 + 12) * 32
    assert row_plane_roofline.least_bytes(whole) == (8 + 20) * 32
    assert row_plane_roofline.least_bytes([]) == 0


def _errors(store):
    """|replay kept in ``store`` - replay in float32| over 24 steps of the
    cell's two id patterns, on 64 seeded rows of 256 columns."""
    rng = np.random.default_rng(7)
    init = (0.02 * rng.standard_normal((64, 256))).astype(np.float32)
    out = []
    for table, counts in ((0, [rng.poisson(0.4, 64) for _ in range(24)]),
                          (1, [np.ones(64, np.int64)] * 24)):
        exact, _ = adagrad_rows.replay(init, counts, table)
        got, _ = adagrad_rows.replay(init, counts, table, store=store)
        out.append(np.abs(got.astype(np.float64) - exact))
    return out


def test_the_tolerance_refuses_bfloat16_and_a_dropped_step():
    tol = cells.load_cell("lm_vocab_steps").workload["tolerance"]
    assert all(not e.any() for e in _errors(np.float32))
    for err in _errors(ml_dtypes.bfloat16):     # read: worst 1.4e-3, 3.3e-3
        assert (err.max() > 5 * tol["worst_abs"]
                and np.mean(err <= tol["entry_abs"]) < 0.5)
    # one step left out of 24: every row it named is a whole step off
    rng = np.random.default_rng(8)
    init = (0.02 * rng.standard_normal((64, 256))).astype(np.float32)
    counts = [np.ones(64, np.int64)] * 24
    full, _ = adagrad_rows.replay(init, counts, 1)
    short, _ = adagrad_rows.replay(init, counts[:-1], 1)
    assert np.abs(full - short).max() > 10 * tol["worst_abs"]
    # repeats left unsummed (a row named k times in a step, applied once).
    # The count has to change from step to step to show: AdaGrad's step is
    # the same for every constant multiple of the gradient
    named = [rng.poisson(2.0, 64) for _ in range(24)]
    summed, _ = adagrad_rows.replay(init, named, 0)
    once, _ = adagrad_rows.replay(init, [np.minimum(c, 1) for c in named], 0)
    err = np.abs(summed - once)
    assert err.max() > 5 * tol["worst_abs"]
    assert np.mean(err <= tol["entry_abs"]) < 0.5
