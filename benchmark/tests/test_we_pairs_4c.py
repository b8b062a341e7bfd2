"""The cell ``we_pairs_4c``: its files and the lists it joined; its three
readers on a hand-made run; ``init_rows`` against the reference's table;
its check, which refuses a replay with one shard's updates dropped and one
kept in bfloat16; and its rehearsal on four virtual CPU devices."""

import json

import ml_dtypes
import numpy as np
import pytest

from benchmark.harness import cells
from benchmark.harness.run_record import Run
from benchmark.layer_metrics import (we_busy_spread_pct,
                                     we_collective_busy_pct,
                                     we_hot_shard_tokens_pct)
from benchmark.reference import sgns_adagrad
from benchmark.runners import we_app_sharded
from benchmark.tests.test_last_line import _run

CELL = cells.load_cell("we_pairs_4c")
NEW = ("we_hot_shard_tokens_pct", "we_collective_busy_pct",
       "we_busy_spread_pct")
JOINED = ("train_items_per_s", "window_compiles", "host_cpu_cores",
          "custom_call_busy_pct", "top_op_busy_pct", "device_idle_pct",
          "loader_wait_pct", "block_host_ms", "prepare_host_s")


def test_the_files_and_the_lists_the_cell_joined():
    bench = cells.load_benchmark()
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == ["tables_rounds_4c", "we_pairs_4c"]
    assert len(four) <= len(bench["workloads"]) // 4     # two of eight
    cfg, sibling = CELL.config, cells.load_cell("we_pairs").config
    assert cfg["runner"] == "we_app_sharded" and CELL.chips == 4
    assert cfg["options"] == sibling["options"]      # letter for letter
    assert cfg["corpus"] == sibling["corpus"]
    assert cfg["vocabulary"] == 83_886 * 100 == 2 ** 23 - 8
    rows = -(-cfg["vocabulary"] // 4)
    assert rows == 2_097_150
    assert 4 * cfg["vocabulary"] * 512 > 16_909_334_528 > 4 * rows * 512
    lists = {m["name"]: m.get("workloads")
             for m in bench["end_to_end"] + bench["per_layer"]}
    for name in JOINED:
        assert "we_pairs_4c" in lists[name], name
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    for name in NEW:
        assert lists[name] == ["we_pairs_4c"]
        assert layers[name] in ("app loop", "row ops and kernels", "device")
    reported = {m["name"] for m in CELL.per_layer}
    assert {"setup_compiled_programs", "hbm_peak_gb", "tables_create_s",
            *NEW, *JOINED[1:]} == reported
    mix = CELL.traffic
    assert mix["options"] == {"device_pairs": 1} and mix["traced_epochs"] == 1
    assert mix["nominal_items_per_s"] % 1000 == 0


def _hand_made_run():
    """Four chips, a window of 1,000 ns: chip 0 busy 800 ns, 200 of them
    in an all-reduce inside a ``while``; chips 1 to 3 busy 600 ns, 300 of
    them in the all-reduce."""
    devices = []
    for chip in range(4):
        busy, wire = (800, 200) if chip == 0 else (600, 300)
        devices.append({"name": f"/device:TPU:{chip}", "line": "XLA Ops",
                        "ops": [["while.1", 100, busy, "other"],
                                ["fusion.3", 100, busy - wire, "other"],
                                ["all-reduce.2", 100 + busy - wire, wire,
                                 "collective"]]})
    run = Run(cell=None, seed=0, seconds=1.0, traced=True, rehearsal=False,
              trace={"devices": devices, "host": [], "window": [0, 1000]})
    run.counters_before = {"we.block.tokens.shard0": {"value": 1000.0},
                           "we.blocks": {"value": 3.0}}
    run.counters_after = {"we.block.tokens.shard0": {"value": 1900.0},
                          "we.block.tokens.shard1": {"value": 60.0},
                          "we.block.tokens.shard2": {"value": 30.0},
                          "we.block.tokens.shard3": {"value": 10.0},
                          "we.blocks": {"value": 6.0}}
    return run


def test_the_three_readers_on_a_hand_made_run():
    run = _hand_made_run()
    assert we_hot_shard_tokens_pct.read(run) == pytest.approx(90.0)
    # the busiest chip's own share, not the sum over the chips (1,100 of
    # 2,600 ns: 42.3 %)
    assert we_collective_busy_pct.read(run) == pytest.approx(25.0)
    assert we_busy_spread_pct.read(run) == pytest.approx(25.0)


def test_the_readers_find_nothing_without_their_sources():
    """The parent has no token counters; an untraced run has no trace; one
    chip has no spread and no collective."""
    run = _hand_made_run()
    run.counters_before = run.counters_after = {"we.blocks": {"value": 3.0}}
    assert we_hot_shard_tokens_pct.read(run) is None
    run.trace["devices"] = run.trace["devices"][:1]
    run._summary = None
    assert we_busy_spread_pct.read(run) is None
    assert we_collective_busy_pct.read(run) == pytest.approx(25.0)
    run.trace = None
    assert we_busy_spread_pct.read(run) is None
    assert we_collective_busy_pct.read(run) is None


def test_init_rows_are_the_reference_tables_rows():
    for seed in (0, 2 ** 31 + 77):
        table = sgns_adagrad.init_input(30_000, 128, seed)
        ids = np.unique(np.r_[0, 1, 29_999, np.random.default_rng(
            1).integers(0, 30_000, 500)]).astype(np.int32)
        assert np.array_equal(we_app_sharded.init_rows(ids, 128, seed),
                              table[ids])
    with pytest.raises(ValueError):
        we_app_sharded.init_rows(np.arange(3), 127, 0)


def test_the_idle_sample_is_a_quarter_a_shard_and_names_no_token():
    rng = np.random.default_rng(2)
    named = np.unique(np.r_[rng.integers(0, 1000, 900),     # shard 0 hot
                            rng.integers(1000, 4000, 200)])
    idle = we_app_sharded.idle_sample(rng, 4000, 1000, named, 256)
    assert not np.isin(idle, named).any() and (np.diff(idle) > 0).all()
    assert np.bincount(idle // 1000).tolist() == [64, 64, 64, 64]


# -- the check -----------------------------------------------------------------

BLOCK, SHARDS, DIM, SEED = 1000, 4, 128, 37


def _replay(store=np.float32, dropped=None):
    """What the four-shard tables hold after an epoch, by a numpy stand-in
    for the system: tables kept in ``store``; every named word's input row
    moved by a step and its accumulator fed, but for the words of shard
    ``dropped``; idle words untouched. -> (named, idle) as the runner
    samples them."""
    rng = np.random.default_rng(5)
    vocab = BLOCK * SHARDS
    table = sgns_adagrad.init_input(vocab, DIM, SEED).astype(store)
    g2 = np.zeros((vocab, DIM), store)
    tokens = np.unique(np.r_[rng.integers(0, BLOCK, 600),
                             rng.integers(BLOCK, vocab, 300)]).astype(
                                 np.int32)
    live = tokens[tokens // BLOCK != dropped] if dropped is not None \
        else tokens
    grad = rng.standard_normal((len(live), DIM)).astype(np.float32) * 1e-3
    g2[live] = (grad * grad).astype(store)
    table[live] = (table[live].astype(np.float32) + 0.025 * grad
                   / np.sqrt(grad * grad + 1e-12)).astype(store)

    def sample(ids):
        rows = table[ids].astype(np.float32)
        acc = g2[ids].astype(np.float32)
        return {"ids": ids, "rows": rows, "g2": acc, "host_rows": rows,
                "host_g2": acc,
                "init": we_app_sharded.init_rows(ids, DIM, SEED)}
    idle = we_app_sharded.idle_sample(rng, vocab, BLOCK, tokens, 256)
    return sample(tokens), sample(idle)


def _correct(named, idle) -> bool:
    return all(held for held, _ in we_app_sharded.shard_verdicts(
        named, idle, BLOCK, SHARDS, CELL.workload["moved_share_min"]))


def test_the_check_holds_the_system_and_refuses_its_two_forgeries():
    assert _correct(*_replay())
    # one shard's updates dropped: its words did not move
    for shard in range(SHARDS):
        named, idle = _replay(dropped=shard)
        verdicts = we_app_sharded.shard_verdicts(
            named, idle, BLOCK, SHARDS, CELL.workload["moved_share_min"])
        assert [held for held, _ in verdicts] == [False, True, True, True]
    # tables kept in bfloat16, the precision below the stated float32: the
    # rows no token names are no longer the reference's initial rows
    named, idle = _replay(store=ml_dtypes.bfloat16)
    verdicts = we_app_sharded.shard_verdicts(
        named, idle, BLOCK, SHARDS, CELL.workload["moved_share_min"])
    assert [held for held, _ in verdicts] == [True, True, False, True]


def test_the_check_refuses_a_host_read_that_differs_and_a_stray_write():
    named, idle = _replay()
    named["host_rows"] = named["host_rows"].copy()
    named["host_rows"][7, 3] += 1e-7
    assert not _correct(named, idle)
    named, idle = _replay()
    idle["g2"] = idle["g2"].copy()
    idle["g2"][11, 0] = 1e-12       # an accumulator row no token fed
    assert not _correct(named, idle)
    # a row that moved with no gradient behind it
    named, idle = _replay()
    named["g2"] = named["g2"].copy()
    named["g2"][5] = 0.0
    assert not _correct(named, idle)


def test_rehearsal_runs_on_four_virtual_devices_and_ends_correct():
    res = _run("--workload", "we_pairs_4c", "--seed", str(2 ** 31 + 37),
               "--seconds", "1", "--trace", "1", "--rehearsal")
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["rehearsal"] is True and line["device"]["count"] == 4
    allowed = {m["name"]: m["unit"] for m in CELL.per_layer}
    assert set(line["metrics"]) <= set(allowed)
    assert line["metrics"]["window_compiles"]["value"] == 0.0
    assert line["metrics"]["we_collective_busy_pct"]["value"] > 0.0
    assert 25.0 <= line["metrics"]["we_hot_shard_tokens_pct"]["value"] <= 100
    for name in ("setup_compiled_programs", "tables_create_s",
                 "prepare_host_s", "block_host_ms"):
        assert name in line["metrics"], name
    checks = [ln for ln in res.stdout.splitlines() if "check:" in ln]
    assert len(checks) == 8 and all("ok:" in ln for ln in checks)
