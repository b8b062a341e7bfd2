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
