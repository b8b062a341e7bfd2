"""The cell ``we_host_pipeline``: the files and lists it joined (asked by
membership: a later cell or metric appended after it must not fail this
file), each new reader on a hand-made run, what its check refuses at a
small size on the CPU (the sequential round, a prefetch two blocks deep,
tables kept in bfloat16), and its rehearsal's last line."""

import json
import os
import tempfile

import numpy as np
import pytest

from benchmark.harness import cells, traffic
from benchmark.harness.run_record import Run
from benchmark.layer_metrics import (we_engine_window_ms_mean,
                                     we_fetch_exposed_pct,
                                     we_host_rows_mb_per_block,
                                     we_prefetched_blocks_pct, we_push_pct)
from benchmark.runners import we_app_pipeline
from benchmark.tests.test_last_line import _run

NAME = "we_host_pipeline"
CELL = cells.load_cell(NAME)
NEW = {"we_fetch_exposed_pct": ("app loop", "program_span", "lower"),
       "we_push_pct": ("app loop", "program_span", "lower"),
       "we_prefetched_blocks_pct": ("app loop", "program_counter", "higher"),
       "we_host_rows_mb_per_block": ("tables", "program_counter", "lower"),
       "we_engine_window_ms_mean": ("worker verbs and engine",
                                    "program_counter", "lower")}
JOINED = ("train_items_per_s", "window_compiles", "host_cpu_cores",
          "loader_wait_pct", "block_host_ms", "device_idle_pct",
          "top_op_busy_pct", "custom_call_busy_pct", "prepare_host_s")


def test_the_files_and_the_lists_the_cell_joined():
    bench = cells.load_benchmark()
    entry = next(w for w in bench["workloads"] if w["name"] == NAME)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "we-sgns-pipeline-2097k-128", "epochs_host_pipeline", 1)
    assert entry["why"] == CELL.workload["why"]
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    listed = next(c for c in bench["configs"]
                  if c["name"] == entry["config"])
    cfg, sibling = CELL.config, cells.load_cell("we_pairs").config
    assert listed["source"] == cfg["source"] != sibling["source"]
    assert listed["reduced"] == cfg["reduced"] == sibling["reduced"]
    assert cfg["runner"] == "we_app_pipeline" and CELL.chips == 1
    # we-sgns-2097k-128 letter for letter but the mode
    both = {**sibling["options"], **cfg["options"]}
    differ = {k for k in both
              if cfg["options"].get(k) != sibling["options"].get(k)}
    assert differ == {"is_pipeline", "device_plane", "device_pairs"}
    assert (cfg["options"]["is_pipeline"], cfg["options"]["device_plane"],
            cfg["options"]["device_pairs"]) == (1, 0, 0)
    for key in ("vocabulary", "corpus", "reduced", "cuts"):
        assert cfg[key] == sibling[key], key
    assert cfg["vocabulary"] == 2_097_100
    assert set(sibling["assumed"]) <= set(cfg["assumed"])
    for key in ("source", "deployment", "guarantee"):
        assert cfg[key] and len(cfg["source"]) <= 200
    assert "0..b-2" in cfg["guarantee"]
    lists = {m["name"]: m.get("workloads")
             for m in bench["end_to_end"] + bench["per_layer"]}
    for name in JOINED:
        assert NAME in lists[name], name
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (layer, source, better) in NEW.items():
        m = by_name[name]
        assert m["workloads"] == [NAME] and m["unit"]
        assert (m["layer"], m["source"], m["better"], m["moves"]) == (
            layer, source, better, "train_items_per_s")
        assert cells.load_reader("layer_metrics", name)
    reported = {m["name"] for m in CELL.per_layer}
    assert {"setup_compiled_programs", "hbm_peak_gb", "tables_create_s",
            *NEW, *JOINED[1:]} <= reported
    assert not {m for m in reported if m.startswith("tables_")
                and m != "tables_create_s"}
    assert {"train_items_per_s", "setup_s"} <= {
        m["name"] for m in CELL.end_to_end}
    mix = CELL.traffic
    assert mix["traced_epochs"] == 1 and not mix["options"]
    assert mix["nominal_items_per_s"] % 1000 == 0
    assert mix["nominal_items_per_s_why"]
    # one pass a window at the benchmark's run_seconds
    assert max(1, round(bench["run_seconds"] * mix["nominal_items_per_s"]
                        / cfg["corpus"]["words"])) == 1
    limits = CELL.workload
    assert limits["pairs_rel_tol"] == 0 and limits["sample_rows"] == 4096
    for limit, why in (("loss_rel_tol", "loss_rel_tol_why"),
                       ("row_abs_tol", "row_tol_why"),
                       ("row_max_tol", "row_tol_why")):
        assert limits[limit] > 0 and len(limits[why]) > 40


# -- the readers on a hand-made run ------------------------------------------

def _hand_made(program: bool = True) -> Run:
    """A window of 1000..3000 ns with one pass of three blocks: a first
    block's own fetch (100 ns), two waits for prefetched rows (150 and 50
    ns, the second crossing the window's end by 20), three pushes of 200
    ns; 3 blocks of which 2 prefetched, 6 and 6 MB crossed, four engine
    windows of 8 ms in all. ``program`` False: the parent, which has the
    spans and the engine's histogram and not the three counters."""
    run = Run(cell=None, seed=0, seconds=1.0, traced=True, rehearsal=False)
    run.trace = {"window": [1000, 3000], "devices": [], "host": [
        ["worker.we.fetch", 1100, 100, "loop"],
        ["worker.we.fetch", 1500, 150, "loop"],
        ["worker.we.fetch", 2970, 50, "loop"],
        ["worker.we.push", 1700, 200, "loop"],
        ["worker.we.push.take", 1700, 120, "loop"],
        ["worker.we.push", 2300, 200, "loop"],
        ["worker.we.push", 2700, 200, "loop"],
        ["worker.we.prefetch.issue", 1050, 10, "loop"]]}
    counter = lambda v: {"type": "counter", "value": float(v)}  # noqa: E731
    run.counters_before = {
        "we.blocks": counter(3),
        "server.window.latency_s": {"type": "histogram", "count": 10,
                                    "sum": 0.5}}
    run.counters_after = {
        "we.blocks": counter(6),
        "server.window.latency_s": {"type": "histogram", "count": 14,
                                    "sum": 0.508}}
    if program:
        run.counters_before.update({
            "we.pipeline.prefetched_blocks": counter(2),
            "we.host_plane.fetched_bytes": counter(5e6),
            "we.host_plane.pushed_bytes": counter(5e6)})
        run.counters_after.update({
            "we.pipeline.prefetched_blocks": counter(4),
            "we.host_plane.fetched_bytes": counter(23e6),
            "we.host_plane.pushed_bytes": counter(23e6)})
    return run


def test_the_readers_on_a_hand_made_run():
    run = _hand_made()
    assert we_fetch_exposed_pct.read(run) == pytest.approx(
        100 * (100 + 150 + 30) / 2000)
    assert we_push_pct.read(run) == pytest.approx(100 * 600 / 2000)
    assert we_prefetched_blocks_pct.read(run) == pytest.approx(100 * 2 / 3)
    assert we_host_rows_mb_per_block.read(run) == pytest.approx(12.0)
    assert we_engine_window_ms_mean.read(run) == pytest.approx(2.0)


def test_the_readers_on_the_parent_and_on_nothing():
    parent = _hand_made(program=False)
    assert we_prefetched_blocks_pct.read(parent) is None
    assert we_host_rows_mb_per_block.read(parent) is None
    assert we_fetch_exposed_pct.read(parent) is not None
    assert we_push_pct.read(parent) is not None
    assert we_engine_window_ms_mean.read(parent) is not None
    bare = Run(cell=None, seed=0, seconds=1.0, traced=False,
               rehearsal=False)
    for reader in (we_fetch_exposed_pct, we_push_pct,
                   we_prefetched_blocks_pct, we_host_rows_mb_per_block,
                   we_engine_window_ms_mean):
        assert reader.read(bare) is None
    still = _hand_made()
    still.counters_after = dict(still.counters_before)   # no block, no window
    assert we_prefetched_blocks_pct.read(still) is None
    assert we_host_rows_mb_per_block.read(still) is None
    assert we_engine_window_ms_mean.read(still) is None


# -- what the check refuses ---------------------------------------------------

SIZED = CELL.sized(True)
VOCAB, DIM, SEED, LR = SIZED.config["vocabulary"], 128, 2 ** 31 + 45, 0.025
#: the verdicts, in ``pipeline_verdicts``' order
PAIRS, LOSS, INPUT, OUTPUT, INPUT_G2, OUTPUT_G2 = range(6)


@pytest.fixture(scope="module")
def kept():
    """The blocks of one pass at the rehearsal's size, made by the
    program's own loader."""
    from multiverso_tpu.models.wordembedding.data import (BlockQueue,
                                                          PairGenerator,
                                                          start_loader)
    from multiverso_tpu.models.wordembedding.dictionary import Dictionary
    from multiverso_tpu.models.wordembedding.option import Option
    from multiverso_tpu.models.wordembedding.sampler import Sampler
    cfg, corpus = SIZED.config, SIZED.config["corpus"]
    with tempfile.TemporaryDirectory() as workdir:
        vocab_path, corpus_path, _ = traffic.write_vocab_and_corpus(
            workdir, VOCAB, corpus["words"], corpus["topic_words"],
            corpus["sentence_words"], corpus["nominal_words"], SEED)
        flags = [x for k, v in cfg["options"].items()
                 for x in (f"-{k}", str(v))]
        opt = Option.parse_args(["-train_file", corpus_path, "-read_vocab",
                                 vocab_path, "-seed", str(SEED)] + flags)
        dictionary = Dictionary.load_vocab(vocab_path, set())
        dictionary.RemoveWordsLessThan(1)
        opt.total_words = dictionary.WordCount()
        queue = BlockQueue(capacity=8)
        loader = start_loader(
            opt, dictionary,
            PairGenerator(opt, dictionary,
                          Sampler(dictionary.counts(), seed=SEED), None),
            queue, 1)
        blocks = []
        while (block := queue.pop()) is not None:
            blocks.append(block)
        loader.join()
    assert len(blocks) >= 3
    return blocks


@pytest.fixture(scope="module")
def honest(kept):
    return we_app_pipeline.reference_pass(kept, VOCAB, DIM, SEED, LR)


def _as_system(kept, passed: dict) -> dict:
    """What the runner would have read of a system that left ``passed``."""
    rng = np.random.default_rng(SEED)
    sample = {}
    for name, _, rows_of, place in we_app_pipeline.TABLES:
        space = passed["in_ids" if rows_of == "input_rows" else "out_ids"]
        at = np.sort(rng.choice(len(space), min(4096, len(space)),
                                replace=False))
        sample[name] = (space[at], passed["tables"][place][at])
    return {"loss": passed["loss"] / passed["pairs"],
            "pairs": passed["pairs"], "sample": sample}


def _failed(kept, honest, **fault) -> list:
    passed = we_app_pipeline.reference_pass(kept, VOCAB, DIM, SEED, LR,
                                            **fault)
    return [i for i, (held, _) in enumerate(
        we_app_pipeline.pipeline_verdicts(_as_system(kept, passed), honest,
                                          CELL.workload)) if not held]


def test_the_honest_pass_holds(kept, honest):
    assert _failed(kept, honest) == []
    system = _as_system(kept, honest)
    system["pairs"] += 1
    assert [i for i, (held, _) in enumerate(
        we_app_pipeline.pipeline_verdicts(system, honest, CELL.workload))
        if not held] == [PAIRS]


def test_the_sequential_round_is_refused_by_every_limit(kept, honest):
    assert _failed(kept, honest, prefetch_depth=0) == [
        LOSS, INPUT, OUTPUT, INPUT_G2, OUTPUT_G2]


def test_a_prefetch_two_blocks_deep_is_refused(kept, honest):
    failed = _failed(kept, honest, prefetch_depth=2)
    assert LOSS in failed and OUTPUT in failed


def test_tables_kept_in_bfloat16_are_refused(kept, honest):
    """The precision below the stated float32 comes out as not correct:
    by the rows' limits at least (at this size by the loss's too), and
    never by the pair count, which no precision moves."""
    failed = _failed(kept, honest, dtype="bfloat16")
    assert PAIRS not in failed
    assert {INPUT, OUTPUT, INPUT_G2, OUTPUT_G2} & set(failed)


def test_rehearsal_ends_correct_and_its_last_line_parses():
    res = _run("--workload", NAME, "--seed", str(2 ** 31 + 45),
               "--seconds", "1", "--trace", "1", "--rehearsal")
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["failed"] == 0 and line["attempted"] >= 3
    metrics = line["metrics"]
    assert set(NEW) <= set(metrics)
    assert metrics["window_compiles"]["value"] == 0
    blocks = line["attempted"]
    assert metrics["we_prefetched_blocks_pct"]["value"] == pytest.approx(
        100 * (blocks - 1) / blocks)
    assert metrics["we_host_rows_mb_per_block"]["value"] > 0
    assert 0 < metrics["we_fetch_exposed_pct"]["value"] < 100
    assert 0 < metrics["we_push_pct"]["value"] < 100
    assert os.path.exists(os.path.join(
        cells.BENCH_DIR, "reference", "sgns_adagrad_pipeline.py"))
