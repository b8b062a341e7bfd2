"""The deployment ``criteo1tb-mh-26t-128-share32`` small, on the CPU: 26
AdaGrad tables of one lane tile behind the two device-plane verbs, from
one live row up, every step naming all of them.

* a 26-table world at the configuration's rehearsal counts, driven by bags
  of ids as the cell drives it, equals ``tables/share_reference.replay``
  (the float32 rules of ``updaters/reference.py``, table by table), every
  table compared whole: among them tables of 1, 2 and 5 rows under 8 to 40
  repeated ids, and id buckets larger than the table;
* the counters ``table.device_apply.{pallas,xla,small_table}_verbs``
  against ``ops.row_write`` for each of the 26 real shapes (shapes only,
  nothing allocated);
* the share: a table the system block-shards over four devices holds,
  shard by shard, what ``share_reference.replay_share`` replays for
  servers 0 to 3, and the shares' row counts add up to the table's.
* the inverse map of an apply's repeated ids (``_inverse_of_repeats``, a
  rank scratch on the table): ``np.searchsorted(np.unique(ids), ids)``
  element for element on each of the 26 shares' id sets and on the sets
  that would show a stale, short or shard-local scratch; which applies
  make the scratch and step ``table.device_apply.combined_verbs``.

Tolerance as tests/test_updaters_reference.py: both sides compute in
float32 and differ in the order repeated deltas are summed in and in how
the compiler rounds ``delta / lr`` and ``1 / sqrt``.
"""

import contextlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu import ops
from multiverso_tpu.tables import MatrixTableOption, share_reference
from multiverso_tpu.tables.matrix_table import MatrixServerTable
from multiverso_tpu.telemetry import metrics
from multiverso_tpu.updaters.base import AddOption

RTOL, ATOL = 2e-5, 2e-6
OPTION = dict(learning_rate=0.004, rho=0.1)
with open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "criteo1tb-mh-26t-128-share32.json")) as _f:
    CONFIG = json.load(_f)
PUBLISHED = CONFIG["published"]["num_embeddings_per_feature"]
HOT = CONFIG["published"]["multi_hot_sizes"]
SMALL = CONFIG["rehearsal"]["rows"]
TABLES = range(len(PUBLISHED))
BAGS, STEPS = 8, 3


def _bags(rng, rows: int, hot: int) -> np.ndarray:
    """BAGS bags of ``hot`` ids, the first of a bag skewed to low rows."""
    ids = rng.integers(0, rows, (BAGS, hot))
    ids[:, 0] = np.minimum(ids[:, 0], rng.integers(0, rows, BAGS))
    return ids.astype(np.int32).ravel()


@contextlib.contextmanager
def _world(use_pallas: str, tables):
    """(servers, what the reference holds) by table number, after STEPS
    steps over ``tables`` of the 26 small tables."""
    import multiverso_tpu as mv
    mv.MV_Init([f"-use_pallas={use_pallas}"], devices=jax.devices()[:1])
    try:
        rng = np.random.default_rng(26)
        init = [rng.uniform(-1, 1, (SMALL[t], 128)).astype(np.float32)
                / np.float32(np.sqrt(PUBLISHED[t])) for t in tables]
        servers = [mv.MV_CreateTable(MatrixTableOption(
            num_rows=len(rows), num_cols=128, updater_type="adagrad",
            initializer=lambda shape, rows=rows: rows)).server()
            for rows in init]
        adds = []
        for _ in range(STEPS):
            for i, (t, srv) in enumerate(zip(tables, servers)):
                ids = _bags(rng, SMALL[t], HOT[t])
                rows = srv.device_fetch_rows(ids)
                assert rows.shape == (len(ids), 128)
                delta = jnp.float32(OPTION["learning_rate"]) * (
                    0.25 * rows + jnp.float32(0.01 * (t + 1)))
                srv.device_apply_rows(ids, delta, AddOption(**OPTION))
                adds.append((i, ids, np.asarray(delta)))
        want = share_reference.replay(init, adds, **OPTION)
        yield dict(zip(tables, servers)), dict(zip(tables, want))
    finally:
        mv.MV_ShutDown()


def _table_equals_the_replay(world, table):
    servers, want = world
    srv = servers[table]
    if SMALL[table] <= 5:      # ids that repeat, a bucket over the table
        assert BAGS * HOT[table] >= 8 > SMALL[table]
    np.testing.assert_allclose(srv.raw(), want[table]["data"],
                               rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(
        srv.aux_to_logical("hist", srv.state["aux"]["hist"])[0],
        want[table]["hist"][0], rtol=RTOL, atol=ATOL)


class TestWorld:      # a class each: a world is down before the next is up
    @pytest.fixture(scope="class")
    def world(self):
        with _world("auto", list(TABLES)) as w:
            yield w

    @pytest.mark.parametrize("table", TABLES)
    def test_every_table_equals_the_replay(self, world, table):
        _table_equals_the_replay(world, table)


#: the tables of up to 64 rows: what the chip's row kernel has not met
KERNEL_TABLES = [t for t in TABLES if SMALL[t] <= 64]


class TestWorldOnTheKernel:
    """The same under ``-use_pallas=on``: the row kernel (interpreter
    mode) writes the rows and the history, as on the chip."""

    @pytest.fixture(scope="class")
    def world(self):
        with _world("on", KERNEL_TABLES) as w:
            yield w

    @pytest.mark.parametrize("table", KERNEL_TABLES)
    def test_every_small_table_equals_the_replay(self, world, table):
        _table_equals_the_replay(world, table)


def test_the_world_holds_the_smallest_tables():
    assert {1, 2, 5} <= set(SMALL) and len(SMALL) == 26
    assert sum(PUBLISHED) == 204_184_588 and sum(HOT) == 214
    assert sum(CONFIG["rows"]) == CONFIG["rows_sum"] == 6_380_781


# -- the three counters, on the real shapes ----------------------------------

#: a step's distinct rows a table, as the cell's id law draws them (the
#: middle of 64 sets of one seed; a table's sets stay in one power of two)
DISTINCT = [5445, 1100, 390, 232, 634, 1, 210, 49, 2, 13600, 5180, 8900, 1,
            70, 374, 5, 1, 31, 1, 23700, 188300, 53450, 12000, 406, 4, 2]
WRITES = ["pallas", "small_table", "pallas", "small_table", "small_table",
          "small_table", "small_table", "small_table", "small_table",
          "pallas", "pallas", "small_table", "small_table", "small_table",
          "small_table", "small_table", "small_table", "small_table",
          "small_table", "pallas", "xla", "pallas", "pallas", "small_table",
          "small_table", "small_table"]


def _moved(before, after, kind):
    name = f"table.device_apply.{kind}_verbs"
    return (after.get(name, {}).get("value", 0.0)
            - before.get(name, {}).get("value", 0.0))


@pytest.mark.parametrize("table", TABLES)
def test_apply_counters_follow_the_static_choice(monkeypatch, table):
    """The chip's choice (a TPU backend) for server 0's block of each
    published table at the bucket its distinct rows round up to."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows = share_reference.share_rows(PUBLISHED[table], 32, 0)
    assert rows == CONFIG["rows"][table]
    bucket = max(8, 1 << (DISTINCT[table] - 1).bit_length())
    assert ops.row_write(rows + 1, 128, np.float32, bucket) == WRITES[table]
    srv = object.__new__(MatrixServerTable)      # shapes only
    srv.shard_rows, srv.store_cols, srv.dtype = rows + 1, 128, np.dtype(
        np.float32)
    before = metrics.snapshot()
    srv._count_apply_write(bucket)
    after = metrics.snapshot()
    assert {k: _moved(before, after, k)
            for k in ("pallas", "xla", "small_table")} == {
        k: float(k == WRITES[table]) for k in ("pallas", "xla",
                                               "small_table")}


def test_a_step_of_the_deployment_by_write():
    assert (WRITES.count("pallas"), WRITES.count("xla"),
            WRITES.count("small_table")) == (7, 1, 18)


# -- the share ---------------------------------------------------------------

SHARED_ROWS, SERVERS = 37, 4


@pytest.fixture(scope="class")
def sharded():
    """A 37-row AdaGrad table over four devices after three Adds with
    repeated ids, and the Adds."""
    import multiverso_tpu as mv
    mv.MV_Init([], devices=jax.devices()[:SERVERS])
    try:
        rng = np.random.default_rng(4)
        init = (0.1 * rng.standard_normal((SHARED_ROWS, 128))).astype(
            np.float32)
        srv = mv.MV_CreateTable(MatrixTableOption(
            num_rows=SHARED_ROWS, num_cols=128, updater_type="adagrad",
            initializer=lambda shape: init)).server()
        assert srv.num_servers == SERVERS
        adds = []
        for _ in range(3):
            ids = rng.integers(0, SHARED_ROWS, 48).astype(np.int32)
            delta = (1e-3 * rng.standard_normal((48, 128))).astype(
                np.float32)
            srv.device_apply_rows(ids, jnp.asarray(delta),
                                  AddOption(**OPTION))
            adds.append((0, ids, delta))
        state = jax.tree.map(np.asarray, srv.state)
        yield srv.shard_rows, state, init, adds
    finally:
        mv.MV_ShutDown()


class TestShare:
    @pytest.mark.parametrize("server", range(SERVERS))
    def test_each_shard_holds_what_its_share_replays(self, sharded,
                                                         server):
        shard_rows, state, init, adds = sharded
        held = share_reference.share_rows(SHARED_ROWS, SERVERS, server)
        assert held == (10, 10, 10, 7)[server]
        want = share_reference.replay_share([init], adds, SERVERS, server,
                                            **OPTION)[0]
        first = server * shard_rows         # a shard: its block, a trash row
        np.testing.assert_allclose(state["data"][first: first + held],
                                   want["data"], rtol=RTOL, atol=ATOL)
        np.testing.assert_allclose(state["aux"]["hist"][first: first + held],
                                   want["hist"][0], rtol=RTOL, atol=ATOL)


    def test_the_shares_add_up_and_are_the_whole_replay(self, sharded):
        _, _, init, adds = sharded
        whole = share_reference.replay([init], adds, **OPTION)[0]["data"]
        parts = [share_reference.replay_share([init], adds, SERVERS, s,
                                              **OPTION)[0]["data"]
                 for s in range(SERVERS)]
        np.testing.assert_array_equal(np.concatenate(parts), whole)
        for rows in PUBLISHED + [SHARED_ROWS, 1, 31, 32, 33]:
            assert sum(share_reference.share_rows(rows, 32, s)
                       for s in range(32)) == rows


# -- the inverse map of repeated ids -----------------------------------------

def _bag_law(rng, rows: int, bags: int, hot: int) -> np.ndarray:
    """The cell's id law (benchmark/runners/table_bag_steps.py ``bag_ids``):
    a bag's first id by a log-uniform rank through a permutation, the
    others uniform."""
    ranks = np.floor(np.exp(rng.random(bags) * np.log(rows + 1))).astype(
        np.int64) - 1
    ids = rng.integers(0, rows, (bags, hot))
    ids[:, 0] = rng.permutation(rows)[np.clip(ranks, 0, rows - 1)]
    return ids.astype(np.int32).ravel()


def _shape_only(num_rows: int) -> MatrixServerTable:
    srv = object.__new__(MatrixServerTable)      # no world, no device
    srv.num_rows = num_rows
    return srv


def _inverse_equals_the_search(srv, ids):
    uniq = np.unique(ids)
    inv = srv._inverse_of_repeats(uniq, ids)
    assert inv.dtype == np.int32 and inv.shape == ids.shape
    np.testing.assert_array_equal(inv, np.searchsorted(uniq, ids))
    assert srv._rank_scratch.shape == (srv.num_rows,)


@pytest.mark.parametrize("table", TABLES)
def test_inverse_of_a_step_of_each_share(table):
    """A verb's ids as the deployment draws them: 2,048 bags on server 0's
    block of each published table (table 20: 204,800 ids over 1,250,000
    rows; five tables of one row named 2,048 times or more)."""
    rows = CONFIG["rows"][table]
    ids = _bag_law(np.random.default_rng(34 + table), rows, 2048, HOT[table])
    _inverse_equals_the_search(_shape_only(rows), ids)


def _applies_in_a_row(rng):
    # the second set names rows of the first at other ranks and rows the
    # first never wrote; the third is the first again, the fourth one id
    first = rng.integers(0, 1000, 700)
    return [first, np.concatenate([first[::7] + 1, rng.integers(0, 1000, 90),
                                   first[:50]]) % 1000, first,
            np.full(64, 999)]


ID_SETS = {
    "one_row_2048_times": (1, lambda rng: [np.zeros(2048, np.int64)]),
    "distinct_shuffled": (4096, lambda rng: [rng.permutation(4096)[:3000]]),
    "applies_in_a_row": (1000, _applies_in_a_row),
    "first_and_last_row": (12_000_000, lambda rng: [
        np.array([11_999_999, 0, 11_999_999, 0, 5])]),
}


@pytest.mark.parametrize("case", ID_SETS)
def test_inverse_of_repeats_equals_the_search(case):
    rows, sets = ID_SETS[case]
    srv = _shape_only(rows)
    scratch = None
    for ids in sets(np.random.default_rng(34)):
        _inverse_equals_the_search(srv, ids.astype(np.int32))
        if scratch is None:
            scratch = srv._rank_scratch
            scratch[:] = np.iinfo(np.int32).max     # what was never written
        assert srv._rank_scratch is scratch         # made once, never cleared


APPLIES = {     # ids, delta on the device, verbs that take the device combine
    "repeats_on_device": ([5, 3, 5, 9, 3, 5], True, 1),
    "distinct_on_device": ([9, 3, 5, 0], True, 0),
    "repeats_from_host": ([5, 3, 5, 9, 3, 5], False, 0),
}


@pytest.mark.parametrize("shards", [1, SERVERS])
@pytest.mark.parametrize("case", APPLIES)
def test_the_scratch_is_made_by_the_first_device_combine(case, shards):
    """Whole-number deltas under the default ``+=``: the table equals the
    host's sums bit for bit, on one shard and on four (ids are GLOBAL row
    ids: the scratch is ``num_rows`` long, not a shard's)."""
    import multiverso_tpu as mv
    ids, on_device, combined = APPLIES[case]
    mv.MV_Init([], devices=jax.devices()[:shards])
    try:
        srv = mv.MV_CreateTable(MatrixTableOption(
            num_rows=SHARED_ROWS, num_cols=128)).server()
        assert srv.num_servers == shards
        want = np.zeros((SHARED_ROWS, 128), np.float32)
        rng = np.random.default_rng(34)
        for shift in (0, 27, 14):       # three applies: rows of every shard
            rows = (np.asarray(ids, np.int32) + shift) % SHARED_ROWS
            delta = rng.integers(-9, 10, (len(rows), 128)).astype(np.float32)
            before = metrics.snapshot()
            srv.device_apply_rows(
                rows, jnp.asarray(delta) if on_device else delta)
            assert _moved(before, metrics.snapshot(), "combined") == combined
            np.add.at(want, rows, delta)
        np.testing.assert_array_equal(srv.raw(), want)
        if combined:
            assert srv._rank_scratch.shape == (SHARED_ROWS,)
        else:
            assert "_rank_scratch" not in srv.__dict__
    finally:
        mv.MV_ShutDown()
