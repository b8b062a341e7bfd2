"""The cell ``rec_pooled_steps``: its files and entries exist and agree;
its rehearsal ends ``correct`` with the contract's last line and compiles
nothing in its window; its traffic (exactly ``2,048 x multi_hot_sizes[t]``
positions, lengths that add up, no empty bag, about 1.68 positions a bag);
the plain replay (``advance`` is ``advance_plain`` bit for bit; ``pool``,
``spread`` and ``split_bags``); its four readers on a hand-made run; and
its tolerance, which refuses the replay kept in bfloat16, a dropped step,
a gradient handed to the neighbouring bag, a bag's sum that misses its
last position, a repeat across two bags left unsummed and the neighbouring
table's delta. Membership in ``BENCHMARK.json`` is asked by name, never by
position in a list."""

import json

import ml_dtypes
import numpy as np
import pytest

from benchmark.harness import cells
from benchmark.harness.run_record import Run
from benchmark.layer_metrics import (pooled_plane_roofline,
                                     pooled_positions_per_bag,
                                     pooled_segments_ms_per_step,
                                     pooled_verb_host_ms_mean)
from benchmark.reference import adagrad_rows
from benchmark.reference import pooled_adagrad_rows as ref
from benchmark.runners import table_pooled_steps
from benchmark.tests.test_last_line import _run

NAME = "rec_pooled_steps"
CELL = cells.load_cell(NAME)
OTHER = cells.load_cell("rec_bag_steps")
LR, RHO = CELL.traffic["learning_rate"], CELL.traffic["rho"]
OPT = dict(learning_rate=LR, rho=RHO)
NEW = ("pooled_verb_host_ms_mean", "pooled_segments_ms_per_step",
       "pooled_positions_per_bag", "pooled_plane_roofline")
JOINED = ("tables_window_compiles", "tables_host_cpu_cores",
          "tables_custom_call_busy_pct", "tables_top_op_busy_pct",
          "tables_device_idle_pct", "tables_window_jit_ms",
          "device_calls_per_op", "h2d_mb_per_op", "table_place_pct",
          "table_call_pct")


# -- files and entries ---------------------------------------------------------

def test_the_entries_exist_and_agree_with_the_files():
    bench = cells.load_benchmark()
    by = lambda kind: {e["name"]: e for e in bench[kind]}  # noqa: E731
    entry, work = by("workloads")[NAME], CELL.workload
    cfg = by("configs")[entry["config"]]
    for key in ("config", "traffic", "chips", "why"):
        assert entry[key] == work[key], key
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert cfg["file"] == f"benchmark/configs/{entry['config']}.json"
    assert cfg["source"] == CELL.config["source"] and len(
        cfg["source"]) <= 200
    assert cfg["reduced"] == CELL.config["reduced"] == ["rows"]
    assert CELL.config["name"] == entry["config"]
    assert NAME in by("end_to_end")["table_rows_per_s"]["workloads"]
    layer = by("per_layer")
    for name in NEW:
        assert layer[name]["workloads"] == [NAME], name
        assert layer[name]["moves"] == "table_rows_per_s"
    for name in JOINED:
        assert NAME in layer[name]["workloads"], name
    assert layer["pooled_plane_roofline"]["unit"] == "%"
    # the row verbs' readers find nothing in this cell and do not list it
    for name in ("row_plane_roofline", "device_verb_host_ms_mean",
                 "round_prepare_pct", "apply_combine_ms_per_step"):
        assert NAME not in layer[name]["workloads"], name


def test_the_configuration_is_the_sibling_s_with_the_server_pooling():
    cfg, other = CELL.config, OTHER.config
    for key in ("published", "rows", "rows_sum", "cols", "updater",
                "servers", "server", "reduced", "world_flags", "rehearsal"):
        assert cfg[key] == other[key], key
    assert cfg["cuts"]["rows"].startswith(other["cuts"]["rows"])
    for key in ("dtype", "updater", "init", "ids"):
        assert cfg["assumed"][key] == other["assumed"][key]
    assert cfg["assumed"]["share_of_ids"].startswith(
        other["assumed"]["share_of_ids"])
    assert set(cfg["assumed"]) == set(other["assumed"]) | {"bags",
                                                           "empty_bags"}
    assert cfg["published"]["pooling"] == "sum"
    assert cfg["source"] != other["source"] and "sum pooling" in cfg[
        "source"]
    assert cfg["runner"] == "table_pooled_steps"
    mix, sibling = CELL.traffic, OTHER.traffic
    assert mix["batch"] == cfg["published"]["batch_size"] == 65_536
    for key in ("bags", "id_sets", "learning_rate", "rho",
                "traced_seconds"):
        assert mix[key] == sibling[key], key
    assert "TO BE WRITTEN" not in CELL.workload["tolerance"]["why"]


def test_rehearsal_ends_correct_with_the_contract_line():
    res = _run("--workload", NAME, "--seed", str(2**31 + 59),
               "--seconds", "1", "--trace", "1", "--rehearsal")
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "rehearsal"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1 and line["device"]["platform"] == "cpu"
    allowed = {m["name"]: m["unit"] for m in CELL.per_layer}
    assert set(line["metrics"]) <= set(allowed)
    for name in NEW[:3]:
        assert line["metrics"][name]["unit"] == allowed[name]
    assert line["metrics"]["tables_window_compiles"]["value"] == 0.0
    # one program a verb: 52 a step, no pad, no cut
    assert line["metrics"]["device_calls_per_op"]["value"] == 52.0
    # a share of a chip's peak is never read off a CPU
    assert "pooled_plane_roofline" not in line["metrics"]


# -- the traffic ---------------------------------------------------------------

def test_the_generator_s_counts():
    """One draw of a real step: exactly ``2,048 x multi_hot_sizes[t]``
    positions a table (438,272), lengths that add up, no empty bag, the
    bag counts the issue reckons: about 261,000, 1.68 positions a bag,
    2,016 bags of one for a one-hot table, about 62,650 bags of 3.27 for
    the 100-hot table (each within four standard deviations)."""
    cfg, mix = CELL.config, CELL.traffic
    hot = cfg["published"]["multi_hot_sizes"]
    rng = np.random.default_rng(59)
    positions = bags = 0
    for t, (rows, h) in enumerate(zip(cfg["rows"], hot)):
        ids, lengths = table_pooled_steps.jagged_bags(
            rng, rows, mix["bags"], h, np.arange(rows), mix["batch"])
        assert ids.dtype == lengths.dtype == np.int32
        assert len(ids) == 2_048 * h and lengths.sum() == len(ids)
        assert lengths.min() >= 1                   # no empty bag is sent
        assert ids.min() >= 0 and ids.max() < rows
        mean = 65_536 * (1 - np.exp(-len(ids) / 65_536))
        assert abs(len(lengths) - mean) < 4 * np.sqrt(len(ids)) * 0.3 + 8
        if h == 100:
            assert abs(len(lengths) - 62_650) < 250
            assert 3.25 < len(ids) / len(lengths) < 3.29
        if h == 1:
            assert abs(len(lengths) - 2_016) < 25 and lengths.max() <= 3
        positions, bags = positions + len(ids), bags + len(lengths)
    assert positions == 438_272 == 2_048 * sum(hot)
    assert abs(bags - 261_000) < 1_500 and 1.66 < positions / bags < 1.70
    # the same seed, the same bags
    a = table_pooled_steps.jagged_bags(np.random.default_rng(1), 1000, 64,
                                       3, np.arange(1000), 256)
    b = table_pooled_steps.jagged_bags(np.random.default_rng(1), 1000, 64,
                                       3, np.arange(1000), 256)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))


def test_the_bags_are_the_sibling_s_positions_grouped_by_sample():
    """The multiset of a table's ids is ``bag_ids``' of the same draw, and
    the positions of one sample keep their order (a stable sort)."""
    from benchmark.runners.table_bag_steps import bag_ids
    perm = np.random.default_rng(0).permutation(500)
    ids, lengths = table_pooled_steps.jagged_bags(
        np.random.default_rng(7), 500, 64, 5, perm, 128)
    rng = np.random.default_rng(7)
    flat = bag_ids(rng, 500, 64, 5, perm)
    sample = rng.integers(0, 128, len(flat))
    assert np.array_equal(np.sort(ids), np.sort(flat))
    start = 0
    for s, n in zip(np.unique(sample), lengths):
        assert np.array_equal(ids[start:start + n], flat[sample == s])
        start += n


def test_a_sample_of_bags_holds_the_longest():
    lengths = np.array([1, 3, 2, 5, 1, 1, 4], np.int32)
    where, sub = table_pooled_steps.sample_bags(
        np.random.default_rng(0), lengths, 4)
    assert len(sub) == 4 and 5 in sub and sub.sum() == len(where)
    where, sub = table_pooled_steps.sample_bags(
        np.random.default_rng(0), lengths, 9)
    assert np.array_equal(sub, lengths) and np.array_equal(
        where, np.arange(lengths.sum()))


# -- the plain arithmetic of bags ----------------------------------------------

def test_pool_spread_and_split_bags():
    rng = np.random.default_rng(3)
    lengths = np.array([2, 0, 3, 1, 0])
    ids = np.array([9, 0, 5, 4, 7, 1])
    rows = rng.integers(-8, 9, (10, 4)).astype(np.float32)
    pooled = ref.pool(rows, ids, lengths)
    assert pooled.dtype == np.float32 and pooled.shape == (5, 4)
    assert np.array_equal(pooled[0], rows[9] + rows[0])
    assert np.array_equal(pooled[2], rows[5] + rows[4] + rows[7])
    assert not pooled[1].any() and not pooled[4].any()
    d = rng.integers(-4, 5, (5, 4)).astype(np.float32)
    assert np.array_equal(ref.spread(d, lengths), d[[0, 0, 2, 2, 2, 3]])
    # two servers of five rows each: the partial sums add up to the whole
    total = np.zeros_like(pooled)
    for s in range(2):
        mine, part, bags = ref.split_bags(ids, lengths, 10, 2, s)
        assert part.sum() == len(mine) and len(part) == 5
        total[bags] += ref.pool(rows[5 * s: 5 * s + 5], mine, part)
    assert np.array_equal(total, pooled)
    mine, part, bags = ref.split_bags(ids, lengths, 10, 2, 1,
                                      keep_empty=False)
    assert np.array_equal(mine, [4, 0, 2]) and np.array_equal(part, [1, 2])
    assert np.array_equal(bags, [0, 2])


# -- the plain replay ----------------------------------------------------------

SHAPES = {      # rows, bags, the longest bag
    "one_row_named_by_every_position": (1, 300, 8),
    "two_rows_2048_bags_of_one": (2, 2048, 1),
    "rows_shared_across_bags": (50, 400, 6),
    "mostly_distinct_rows": (5000, 2000, 3),
}


@pytest.mark.parametrize("shape", SHAPES)
def test_advance_is_advance_plain_bit_for_bit(shape):
    rows, bags, longest = SHAPES[shape]
    rng = np.random.default_rng(59)
    lengths = rng.integers(1, longest + 1, bags)
    ids = rng.integers(0, rows, lengths.sum())
    init = rng.random((rows, 24), dtype=np.float32) - np.float32(0.5)
    plain, fast = [init.copy(), np.zeros_like(init)], [
        init.copy(), np.zeros_like(init)]
    bands = [[init[:, c:c + 8].copy(),
              np.zeros((rows, 8), np.float32)] for c in (0, 8, 16)]
    p, scratch = ref.plan(ids, lengths), {}
    for step in range(4):
        ref.advance_plain(*plain, ids, lengths, step, 7, **OPT)
        ref.advance(*fast, p, step, 7, scratch, **OPT)
        for b, (w, h) in enumerate(bands):      # a band of columns alone
            ref.advance(w, h, p, step, 7, scratch,
                        columns=np.arange(8 * b, 8 * b + 8), **OPT)
    assert np.array_equal(plain[0], fast[0])
    assert np.array_equal(plain[1], fast[1])
    assert np.array_equal(np.concatenate([w for w, _ in bands], axis=1),
                          plain[0])
    assert not np.array_equal(plain[0], init)


def test_the_replay_of_the_named_rows_is_the_replay_of_the_table():
    """The runner renumbers the rows some id set names 0..m-1 and replays
    those alone: bit for bit the whole table's replay at those rows, and
    the others never move."""
    rng = np.random.default_rng(4)
    init = rng.random((400, 16), dtype=np.float32) - np.float32(0.5)
    steps = []
    for _ in range(5):
        lengths = rng.integers(1, 5, 60)
        steps.append((rng.choice(400, 150)[rng.integers(0, 150,
                                                         lengths.sum())],
                      lengths))
    named = np.unique(np.concatenate([ids for ids, _ in steps]))
    whole, small = [init.copy(), np.zeros_like(init)], [
        init[named].copy(), np.zeros_like(init[named])]
    for step, (ids, lengths) in enumerate(steps):
        ref.advance_plain(*whole, ids, lengths, step, 3, **OPT)
        ref.advance_plain(*small, np.searchsorted(named, ids), lengths,
                          step, 3, **OPT)
    assert np.array_equal(whole[0][named], small[0])
    idle = np.setdiff1d(np.arange(400), named)
    assert len(idle) and np.array_equal(whole[0][idle], init[idle])


def test_the_pattern_is_the_sibling_s():
    for step, table in ((0, 0), (5, 20), (99, 25)):
        whole = adagrad_rows.pattern(step, 128, table)
        assert np.array_equal(ref.pattern(step, np.arange(128), table),
                              whole)
        assert np.array_equal(ref.pattern(step, [5, 99, 32], table),
                              whole[[5, 99, 32]])


def test_the_pattern_s_sign_is_the_gradient_s():
    """Whatever the pooled row, ``|g|`` is 1/128 at least: no first step
    of a row is taken in AdaGrad's epsilon regime."""
    rng = np.random.default_rng(3)
    init = (8 * rng.standard_normal((40, 16))).astype(np.float32)
    ids, lengths = rng.integers(0, 40, 90), np.full(30, 3)
    for step in range(3):
        w, h = init.copy(), np.zeros_like(init)
        ref.advance_plain(w, h, ids, lengths, step, 4, **OPT)
        named = np.unique(ids)
        # a first step is rho * g / sqrt(g * g + eps), g the summed gradient
        moved = (init - w)[named]
        sign = np.sign(ref.pattern(step, np.arange(16), 4))
        assert (np.sign(moved) == sign[None, :]).all()
        assert (np.abs(moved) > RHO * 0.99).all()       # |g| >> sqrt(eps)
    assert ref.SLOPE * ref.BOUND <= adagrad_rows.AMPLITUDE / 16 / 2


# -- the four readers ----------------------------------------------------------

def _hand_made_run():
    """Two steps of two tables: four pooled fetches of 100 us (prepare 20,
    dispatch 60), four pooled applies of 300 us (prepare 100, dispatch
    150)."""
    host, at = [["bench.window", 0, 4000, "main"]], 0
    fetch, apply = ("server.table.device_fetch_pooled",
                    "server.table.device_apply_pooled")
    for i in range(4):
        if i % 2 == 0:
            host.append(["bench.step", at, 2000, "main"])
        host += [[fetch, at, 100, "main"],
                 [fetch + ".prepare", at, 20, "main"],
                 [fetch + ".dispatch", at + 30, 60, "main"],
                 [apply, at + 200, 300, "main"],
                 [apply + ".prepare", at + 200, 100, "main"],
                 [apply + ".combine", at + 250, 40, "main"],
                 [apply + ".dispatch", at + 320, 150, "main"]]
        at += 1000
    run = Run(cell=None, seed=0, seconds=1.0, traced=True, rehearsal=False,
              trace={"devices": [], "host": host, "window": [0, 4000]})
    run.window = {"attempted": 2, "pooled_verbs": [
        {"positions": 30, "bags": 10, "unique": 20, "row_bytes": 512,
         "state": 2}]}
    run.counters_before = {
        "table.device_fetch_pooled.positions": {"value": 4.0}}
    run.counters_after = {
        "table.device_fetch_pooled.positions": {"value": 34.0},
        "table.device_fetch_pooled.bags": {"value": 18.0}}
    return run


def test_the_readers_on_a_hand_made_run():
    run = _hand_made_run()
    assert pooled_positions_per_bag.read(run) == pytest.approx(30 / 18)
    # the two .prepare spans, 4 x (20 + 100) ns, over two steps
    assert pooled_segments_ms_per_step.read(run) == pytest.approx(
        240e-6)
    assert pooled_verb_host_ms_mean.read(run) == pytest.approx(200e-6)
    # a fetch: 20 distinct rows read, 10 bags written; an apply: 10 bag
    # gradients read, 20 rows and 20 histories read and written
    assert pooled_plane_roofline.least_bytes(run.window["pooled_verbs"]
                                             ) == ((20 + 10)
                                                   + (10 + 4 * 20)) * 512
    assert pooled_plane_roofline.read(run) is None  # no device in the trace


def test_the_readers_find_nothing_in_a_program_without_their_sources():
    """The parent commit has neither the spans nor the counters; a run
    that was not traced has no spans; the sibling's record has no
    ``pooled_verbs``."""
    run = _hand_made_run()
    run.counters_before = run.counters_after = {
        "table.device_apply.rows": {"value": 5.0}}
    run.trace["host"] = [e for e in run.trace["host"]
                         if "pooled" not in e[0]]
    run.window = {"attempted": 2, "row_verbs": []}
    for reader in (pooled_positions_per_bag, pooled_segments_ms_per_step,
                   pooled_verb_host_ms_mean, pooled_plane_roofline):
        assert reader.read(run) is None
    run.trace = None
    for reader in (pooled_segments_ms_per_step, pooled_verb_host_ms_mean,
                   pooled_plane_roofline):
        assert reader.read(run) is None


# -- the tolerance -------------------------------------------------------------

STEPS = 96      # what a 10 s window and its warm-up make, about


def _tables():
    """(table number, initial rows, the bags of each step) of three kinds
    of the deployment's tables, small: a one-row table nearly every bag of
    which is one position (2,048 positions a step); a table of 64 rows
    under 12,288 positions in some 11,000 bags, rows shared across bags
    and now and then repeated inside one; a large table's corner, whose
    rows are named now and then by bags of up to a dozen positions."""
    rng = np.random.default_rng(59)
    pub = CELL.config["published"]["num_embeddings_per_feature"]

    def init(table, rows):
        return ((2 * rng.random((rows, 128), dtype=np.float32) - 1)
                * np.float32(1 / np.sqrt(pub[table])))

    def bags(rows, n, hot, batch):
        return [table_pooled_steps.jagged_bags(
            rng, rows, n, hot, np.arange(rows), batch)
            for _ in range(STEPS)]
    return [(5, init(5, 1), bags(1, 2048, 1, 65_536)),
            (4, init(4, 64), bags(64, 256, 6, 8_192)),
            (20, init(20, 2048), bags(2048, 16, 100, 512))]


def _refused(err) -> bool:
    """The runner's verdict on a table's errors, by either limit."""
    tol = CELL.workload["tolerance"]
    return bool(err.max() > tol["worst_abs"]
                or np.mean(err <= tol["entry_abs"]) < tol["entry_share"])


def _replay(table, init, steps, skip=None, **kw):
    w, h = np.array(init, np.float32), np.zeros_like(init)
    if "store" in kw:
        w = w.astype(kw["store"]).astype(np.float32)
    for step, (ids, lengths) in enumerate(steps):
        if step != skip:
            ref.advance_plain(w, h, ids, lengths, step, table, **OPT, **kw)
    return w.astype(np.float64)


@pytest.mark.parametrize("kind", range(3))
def test_the_tolerance_refuses_bfloat16_a_dropped_step_and_a_neighbour(kind):
    table, init, steps = _tables()[kind]
    exact = _replay(table, init, steps)
    assert not _refused(np.abs(_replay(table, init, steps) - exact))
    # rows and history kept in bfloat16, the precision below the stated one
    assert _refused(np.abs(_replay(table, init, steps,
                                   store=ml_dtypes.bfloat16) - exact))
    # the last step left out
    assert _refused(np.abs(_replay(table, init, steps, skip=STEPS - 1)
                           - exact))
    # the delta made for the next table of the step applied to this one
    assert _refused(np.abs(_replay(table + 1, init, steps) - exact))


@pytest.mark.parametrize("fault", ["neighbour_bag", "miss_last",
                                   "across_unsummed"])
@pytest.mark.parametrize("kind", (1, 2))
def test_the_tolerance_refuses_a_wrong_bag(kind, fault):
    """On tables whose bags hold several positions and share rows (64 rows
    under bags of up to six positions; 2,048 rows under bags of up to a
    dozen): a bag's gradient handed to the bag after it, a bag's sum that
    misses its last position, a row named by several bags that takes the
    last one's contribution alone."""
    table, init, steps = _tables()[kind]
    exact = _replay(table, init, steps)
    assert _refused(np.abs(_replay(table, init, steps, **{fault: True})
                           - exact))
