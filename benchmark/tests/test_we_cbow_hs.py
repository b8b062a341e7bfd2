"""The cell ``we_cbow_hs``: its files and the lists it joined (by
membership); its three readers on a hand-made run; its check, which holds
an honest pass on other windows and refuses, each by at least one limit,
tables kept in bfloat16, a context summed and not averaged, labels ``c``
for ``1 - c``, a path cut one node short and the repeats of a lane-batch
left unsummed; and its rehearsal's last line."""

import json

import ml_dtypes
import numpy as np
import pytest

from benchmark.harness import cells
from benchmark.harness.run_record import Run
from benchmark.layer_metrics import (hs_path_fill_pct, hs_table_gb,
                                     huffman_build_s)
from benchmark.reference import cbow_hs_adagrad as ref
from benchmark.runners import we_app_hs
from benchmark.tests.test_last_line import _run

CELL = cells.load_cell("we_cbow_hs")
NEW = {"hs_path_fill_pct": "updaters and fused steps",
       "huffman_build_s": "entry points", "hs_table_gb": "device"}
JOINED = ("train_items_per_s", "window_compiles", "host_cpu_cores",
          "custom_call_busy_pct", "top_op_busy_pct", "device_idle_pct",
          "loader_wait_pct", "block_host_ms", "prepare_host_s")


def test_the_files_and_the_lists_the_cell_joined():
    bench = cells.load_benchmark()
    assert len(bench["workloads"]) == len(bench["configs"]) == 9
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) == 2 <= len(bench["workloads"]) // 4
    cfg, sibling = CELL.config, cells.load_cell("we_pairs").config
    assert cfg["runner"] == "we_app_hs" and CELL.chips == 1
    differ = {k for k in cfg["options"]
              if cfg["options"][k] != sibling["options"][k]}
    assert differ == {"cbow", "hs", "negative"}
    assert (cfg["options"]["cbow"], cfg["options"]["hs"],
            cfg["options"]["negative"]) == (1, 1, 0)
    for key in ("vocabulary", "corpus", "reduced", "cuts"):
        assert cfg[key] == sibling[key], key     # letter for letter
    assert cfg["vocabulary"] == 2_097_100
    assert cfg["huffman"]["path_table_bytes"] == (
        2_097_100 * (4 * cfg["huffman"]["longest_code"] + 4 + 4))
    lists = {m["name"]: m.get("workloads")
             for m in bench["end_to_end"] + bench["per_layer"]}
    for name in JOINED:
        assert "we_cbow_hs" in lists[name], name
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    for name, layer in NEW.items():
        assert lists[name] == ["we_cbow_hs"] and layers[name] == layer
    reported = {m["name"] for m in CELL.per_layer}
    assert {"setup_compiled_programs", "hbm_peak_gb", "tables_create_s",
            *NEW, *JOINED[1:]} == reported
    assert {m["name"] for m in CELL.end_to_end} == {"train_items_per_s",
                                                    "setup_s"}
    mix = CELL.traffic
    assert mix["options"] == {"device_pairs": 1} and mix["traced_epochs"] == 1
    assert mix["nominal_items_per_s"] % 1000 == 0
    for limit in ("loss_rel_tol", "root_rel_tol", "eval_rel_tol",
                  "moved_share_min"):
        assert CELL.workload[limit] > 0 and CELL.workload[limit + "_why"]


def _hand_made_run(moved: bool = True):
    run = Run(cell=None, seed=0, seconds=1.0, traced=True, rehearsal=False)
    run.counters_before = {
        "we.hs.path_lanes.valid": {"type": "counter", "value": 1000.0},
        "we.hs.path_lanes.padded": {"type": "counter", "value": 2700.0}}
    run.counters_after = {
        "we.hs.path_lanes.valid": {"type": "counter",
                                   "value": 1000.0 + 1550.0 * moved},
        "we.hs.path_lanes.padded": {"type": "counter",
                                    "value": 2700.0 + 2700.0 * moved},
        "we.prepare.huffman_s": {"type": "gauge", "value": 4.5},
        "we.hs.table_bytes": {"type": "gauge", "value": 243_263_600.0}}
    return run


def test_the_three_readers_on_a_hand_made_run():
    run = _hand_made_run()
    assert hs_path_fill_pct.read(run) == pytest.approx(100 * 1550 / 2700)
    assert huffman_build_s.read(run) == 4.5
    assert hs_table_gb.read(run) == pytest.approx(0.2432636)


def test_the_readers_find_nothing_without_their_sources():
    """The parent has neither the counters nor the gauges; a window in
    which no block ran moves no counter."""
    assert hs_path_fill_pct.read(_hand_made_run(moved=False)) is None
    run = _hand_made_run()
    run.counters_before = run.counters_after = {"we.blocks": {"value": 3.0}}
    for reader in (hs_path_fill_pct, huffman_build_s, hs_table_gb):
        assert reader.read(run) is None


# -- the check -----------------------------------------------------------------

VOCAB, DIM, BATCH, WINDOW, SEED, LR = 2000, 32, 512, 5, 41, 0.025
LIMITS = CELL.sized(True).workload      # the rehearsal's, for a pass this small


class _Opt:
    embedding_size, seed, window_size, pair_batch_size = DIM, SEED, WINDOW, BATCH


def _pass_inputs():
    """Counts in a dictionary's order and three blocks of sentences of 20
    words (one of them a lone word, which is no example)."""
    rng = np.random.default_rng(8)
    counts = np.sort(np.maximum(1, (1e6 / np.arange(1, VOCAB + 1))
                                .astype(np.int64)))[::-1].copy()
    p = counts / counts.sum()
    blocks, sentence = [], 0
    for words in (3000, 3000, 2001):
        ids = rng.choice(VOCAB, words, p=p).astype(np.int32)
        sent = (sentence + np.arange(words) // 20).astype(np.int32)
        sentence = int(sent[-1]) + 1
        blocks.append((ids, sent))
    return counts, blocks


COUNTS, BLOCKS = _pass_inputs()
TREE = ref.huffman_tree(COUNTS)


def _train(windows_seed: int, **kw) -> dict:
    return ref.train_pass(BLOCKS, COUNTS, DIM, SEED, LR, WINDOW, BATCH,
                          np.random.default_rng(windows_seed),
                          **{"tree": TREE, **kw})


REFERENCE = _train(1)
REFERENCE["eval_loss"] = we_app_hs.loss_at_rate_0(
    REFERENCE, BLOCKS[:1], COUNTS, _Opt, TREE, 4)


def _system(result: dict, store=np.float32) -> dict:
    """What the runner reads off the tables, from a pass's result standing
    in for the system."""
    flags = lambda rows: np.isin(                           # noqa: E731
        np.arange(VOCAB), result["out_ids"][rows.any(axis=1)])
    at = int(np.searchsorted(result["out_ids"], VOCAB - 2))
    root = {"ids": np.array([VOCAB - 2], np.int32),
            "rows": result["eo"][at:at + 1], "g2": result["eo_g2"][at:at + 1]}
    named = np.unique(np.concatenate([ids for ids, _ in BLOCKS]))
    ids = we_app_hs.idle_sample(np.random.default_rng(3), VOCAB, VOCAB,
                                named, 256)
    init = we_app_hs.init_rows(ids, DIM, SEED)
    idle = {"ids": ids, "init": init,
            "rows": init.astype(store).astype(np.float32),
            "g2": np.zeros_like(init)}
    for sample in (root, idle):
        sample["host_rows"], sample["host_g2"] = sample["rows"], sample["g2"]
    return {"examples": result["examples"],
            "loss": result["loss"] / result["examples"],
            "out_moved": flags(result["eo"]), "out_fed": flags(result["eo_g2"]),
            "root": root, "idle": idle,
            "eval_loss": we_app_hs.loss_at_rate_0(result, BLOCKS[:1], COUNTS,
                                                  _Opt, TREE, 4)}


def _failed(system: dict) -> list:
    """The numbers of the verdicts that did not hold."""
    return [i for i, (held, _) in enumerate(
        we_app_hs.hs_verdicts(system, REFERENCE, LIMITS)) if not held]


EXAMPLES, LOSS, OUTSIDE, INSIDE, ROOT, EVAL, IDLE, HOST = range(8)


def test_the_check_holds_an_honest_pass_on_other_windows():
    for windows_seed in (2, 3):
        assert _failed(_system(_train(windows_seed))) == []
    assert REFERENCE["examples"] == sum(len(i) for i, _ in BLOCKS) - 1


def test_it_refuses_tables_kept_in_bfloat16():
    """The precision below the stated float32: the words no token names no
    longer hold the reference's initial rows (one limit, not each)."""
    got = _train(2, store=ml_dtypes.bfloat16)
    assert IDLE in _failed(_system(got, store=ml_dtypes.bfloat16))


def test_it_refuses_a_context_summed_and_not_averaged():
    def summed(in_rows, imask):
        return (in_rows * imask[:, :, None]).sum(axis=1)
    assert ROOT in _failed(_system(
        _train(2, step=ref.make_step(hidden=summed))))


def test_it_refuses_labels_c_for_one_less_c():
    """The mirror image: the same loss, the same examples, the same rows
    moved; the tables read at rate 0 under the true labels show it."""
    parent, code = TREE
    got = _train(2, tree=(parent, [1 - c for c in code]))
    assert _failed(_system(got)) == [EVAL]


def test_it_refuses_a_path_cut_one_node_short(monkeypatch):
    whole = ref.paths

    def short(tree, words):
        points, codes, lengths = whole(tree, words)
        return points, codes, np.maximum(lengths - 1, 0)
    monkeypatch.setattr(ref, "paths", short)
    got = _train(2)
    monkeypatch.undo()
    failed = _failed(_system(got))
    assert INSIDE in failed and LOSS in failed


def test_it_refuses_the_repeats_of_a_lane_batch_left_unsummed():
    def last_one_wins(like, ids, grads):
        import jax.numpy as jnp
        return jnp.zeros_like(like).at[ids].set(grads)
    got = _train(2, step=ref.make_step(by_row=last_one_wins))
    assert ROOT in _failed(_system(got))


def test_it_refuses_a_stray_write_and_a_host_read_that_differs():
    system = _system(_train(2))
    system["out_fed"] = system["out_fed"].copy()
    system["out_fed"][VOCAB - 1] = True         # row V - 1 is no node
    assert _failed(system) == [OUTSIDE, INSIDE]
    system = _system(_train(2))
    system["root"] = {**system["root"],
                      "host_g2": system["root"]["g2"] * (1 + 1e-7)}
    assert _failed(system) == [HOST]


def test_rehearsal_ends_correct_and_its_last_line_parses():
    res = _run("--workload", "we_cbow_hs", "--seed", str(2 ** 31 + 39),
               "--seconds", "1", "--trace", "1", "--rehearsal")
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    metrics = line["metrics"]
    assert set(NEW) <= set(metrics) and metrics["window_compiles"]["value"] == 0
    assert 40 < metrics["hs_path_fill_pct"]["value"] < 80
    assert metrics["huffman_build_s"]["value"] > 0
    assert metrics["hs_table_gb"]["value"] > 0
