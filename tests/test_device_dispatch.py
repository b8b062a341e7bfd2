"""What a device-plane verb hands the device: one copy and one call.

``MatrixServerTable`` keeps the device scalars of each distinct AddOption
(``_device_opt``: counters ``table.option_cache.hits`` / ``.misses``), pads
ids on the host and copies them once, and runs no pad program and no slice
program when the batch is its bucket. None of it may change a result: the
device plane is held bit for bit to the host plane's ``AddRows`` /
``GetRows`` on a twin table, for batches at and under their bucket, device
and host deltas, distinct and repeated ids. Deltas are whole numbers, so
sums are exact in any order.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.parallel.mesh import next_bucket
from multiverso_tpu.tables import MatrixTableOption, matrix_table
from multiverso_tpu.telemetry import metrics
from multiverso_tpu.updaters import reference
from multiverso_tpu.updaters.base import AddOption

ROWS, COLS = 200, 128


@pytest.fixture()
def world():
    import multiverso_tpu as mv
    mv.MV_Init(["-num_workers=2"])
    yield mv
    mv.MV_ShutDown()


def _table(world, updater="default", init=None, rows=ROWS, cols=COLS):
    return world.MV_CreateTable(MatrixTableOption(
        num_rows=rows, num_cols=cols, updater_type=updater,
        initializer=None if init is None else (lambda shape: init)))


def _cache_moves():
    """() -> (hits, misses) of the option cache since this call."""
    def read():
        snap = metrics.snapshot()
        return tuple(snap.get(f"table.option_cache.{k}", {}).get("value", 0)
                     for k in ("hits", "misses"))
    h0, m0 = read()

    def moved():
        h, m = read()
        return h - h0, m - m0
    return moved


def _ids(rng, n: int, repeated: bool) -> np.ndarray:
    if not repeated:
        return rng.choice(ROWS, n, replace=False).astype(np.int32)
    uniq = rng.choice(ROWS, n // 3, replace=False)
    return rng.permutation(np.concatenate(
        [uniq, rng.choice(uniq, n - len(uniq))])).astype(np.int32)


# -- (a) the option cache ----------------------------------------------------

def test_equal_option_is_a_hit_and_makes_no_copy(world):
    srv = _table(world).server()
    ids = np.arange(8, dtype=np.int32)
    delta = jnp.ones((8, COLS), jnp.float32)
    moved = _cache_moves()
    srv.device_apply_rows(ids, delta)                   # None: the default
    assert moved() == (0, 1)
    first = srv._device_opt(AddOption())
    assert moved() == (1, 1)
    srv.device_apply_rows(ids, delta, AddOption())      # an equal option
    assert moved() == (2, 1)
    assert srv._device_opt(None) is first               # the kept scalars
    np.testing.assert_array_equal(srv.raw()[:8], 2.0)


def test_device_opt_is_as_jnp_placed_as_the_row_program_declares(world):
    srv = _table(world).server()
    option = AddOption(worker_id=1, momentum=0.9, learning_rate=0.02,
                       rho=0.05, lambda_=0.2)
    kept, plain = srv._device_opt(option), option.as_jnp()
    assert kept.keys() == plain.keys()
    for name, value in plain.items():
        assert kept[name].dtype == value.dtype
        assert kept[name].shape == ()
        assert np.asarray(kept[name]) == np.asarray(value)
        # replicated over the table's mesh: nothing to reshard on entry
        assert kept[name].sharding.is_equivalent_to(
            srv._zoo.mesh_ctx.replicated(), 0)
        assert len(kept[name].sharding.device_set) == srv.num_servers


def test_changed_learning_rate_is_one_miss_exact_and_no_retrace(world):
    """dcasgd reads learning_rate and lambda_; on dyadic values every
    operation of the rule is exact, so table and plain reference agree bit
    for bit, whatever the compiler fuses."""
    rng = np.random.default_rng(3)
    init = rng.integers(-4, 5, (ROWS, COLS)).astype(np.float32) / 4
    srv = _table(world, "dcasgd", init).server()
    want = reference.new_state(init, "dcasgd", num_workers=2)
    ids = rng.choice(ROWS, 64, replace=False).astype(np.int32)
    delta = rng.integers(-2, 3, (64, COLS)).astype(np.float32) / 2
    moved = _cache_moves()
    for _ in range(2):      # the second call sees a program's output state
        srv.device_apply_rows(ids, jnp.asarray(delta),
                              AddOption(learning_rate=0.5, lambda_=0.25))
        reference.apply_rows("dcasgd", want, ids, delta, learning_rate=0.5,
                             lambda_=0.25)
    programs = srv._update_rows._cache_size()
    assert moved() == (1, 1)
    srv.device_apply_rows(ids, jnp.asarray(delta),
                          AddOption(learning_rate=0.125, lambda_=0.25))
    reference.apply_rows("dcasgd", want, ids, delta, learning_rate=0.125,
                         lambda_=0.25)
    assert moved() == (1, 2)                    # one miss for the new rate
    assert srv._update_rows._cache_size() == programs   # and no retrace
    srv.device_apply_rows(ids, jnp.asarray(delta),
                          AddOption(learning_rate=0.5, lambda_=0.25))
    reference.apply_rows("dcasgd", want, ids, delta, learning_rate=0.5,
                         lambda_=0.25)
    assert moved() == (2, 2)                    # the first rate was kept
    np.testing.assert_array_equal(srv.raw(), want["data"])
    np.testing.assert_array_equal(
        srv.aux_to_logical("backup", srv.state["aux"]["backup"]),
        want["backup"])


def test_option_cache_is_bounded(world):
    srv = _table(world).server()
    size = srv._OPT_CACHE_SIZE
    moved = _cache_moves()
    for step in range(2 * size + 3):
        srv._device_opt(AddOption(learning_rate=1.0 / (step + 1)))
    assert len(srv._opt_cache) == size
    assert moved() == (0, 2 * size + 3)
    srv._device_opt(AddOption(learning_rate=1.0 / (2 * size + 3)))
    assert moved() == (1, 2 * size + 3)         # the newest is kept
    srv._device_opt(AddOption(learning_rate=1.0))
    assert moved() == (1, 2 * size + 4)         # the oldest went


def test_host_plane_adds_take_the_same_cache(world):
    table = _table(world, "adagrad")
    ids = np.arange(16, dtype=np.int32)
    delta = np.ones((16, COLS), np.float32)
    option = AddOption(worker_id=1, learning_rate=0.5, rho=0.5)
    moved = _cache_moves()
    table.AddRows(ids, delta, option)
    table.AddRows(ids, delta, option)
    table.Add(np.ones((ROWS, COLS), np.float32), option)
    assert moved() == (2, 1)


# -- (b) at the bucket and under it, bit for bit with the host plane ---------

@pytest.mark.parametrize("repeated", [False, True],
                         ids=["distinct", "repeated"])
@pytest.mark.parametrize("on_device", [True, False],
                         ids=["device_delta", "host_delta"])
@pytest.mark.parametrize("batch", [64, 40], ids=["bucket", "under"])
@pytest.mark.parametrize("updater", ["default", "adagrad"])
def test_device_plane_equals_host_plane(world, updater, batch, on_device,
                                        repeated):
    assert (next_bucket(batch) == batch) == (batch == 64)
    rng = np.random.default_rng(batch + 2 * on_device + repeated)
    init = rng.integers(-8, 9, (ROWS, COLS)).astype(np.float32)
    dev, host = (_table(world, updater, init) for _ in range(2))
    srv = dev.server()
    for _ in range(2):
        ids = _ids(rng, batch, repeated)
        delta = rng.integers(-3, 4, (batch, COLS)).astype(np.float32)
        fetched = srv.device_fetch_rows(ids)
        assert isinstance(fetched, jax.Array)
        assert fetched.shape == (batch, COLS)
        np.testing.assert_array_equal(np.asarray(fetched), host.GetRows(ids))
        given = jnp.asarray(delta) if on_device else delta
        srv.device_apply_rows(ids, given)
        host.AddRows(ids, delta)
        # the row program donates the state alone
        np.testing.assert_array_equal(np.asarray(given), delta)
    np.testing.assert_array_equal(srv.raw(), host.server().raw())
    for name, leaf in srv.state["aux"].items():
        np.testing.assert_array_equal(
            np.asarray(leaf), np.asarray(host.server().state["aux"][name]))


# -- (c) no program that moves nothing ---------------------------------------

@pytest.mark.parametrize("repeated", [False, True],
                         ids=["distinct", "repeated"])
def test_batch_at_its_bucket_builds_and_runs_no_pad_program(world,
                                                            monkeypatch,
                                                            repeated):
    # 72 columns: no other test compiles a pad at this width
    srv = _table(world, "adagrad", cols=72).server()
    rng = np.random.default_rng(11)
    before = matrix_table._pad_row_batch._cache_size()
    assert not hasattr(matrix_table, "_pad_id_batch")   # ids pad on the host
    ids = _ids(rng, 64, repeated)
    delta = jnp.ones((64, 72), jnp.float32)
    srv.device_apply_rows(ids, delta)
    if not repeated:    # (a host delta's repeats combine to a shorter batch)
        srv.device_apply_rows(ids, np.ones((64, 72), np.float32))
    assert matrix_table._pad_row_batch._cache_size() == before

    def no_pad(*args, **kwargs):
        raise AssertionError("a pad program ran with nothing to pad")
    monkeypatch.setattr(matrix_table, "_pad_row_batch", no_pad)
    srv.device_apply_rows(ids, delta)
    monkeypatch.undo()
    # under the bucket the delta is padded on the device, as before
    under = 48 if repeated else 40      # a shape no other case compiles
    srv.device_apply_rows(ids[:under], delta[:under])
    assert matrix_table._pad_row_batch._cache_size() == before + 1


@pytest.mark.parametrize("batch", [64, 40], ids=["bucket", "under"])
def test_fetch_slices_only_under_the_bucket(world, monkeypatch, batch):
    srv = _table(world).server()
    gather, outputs = srv._gather_rows, []

    def recording(data, aux, ids):
        assert ids.shape == (64,)           # padded on the host
        outputs.append(gather(data, aux, ids))
        return outputs[-1]
    monkeypatch.setattr(srv, "_gather_rows", recording)
    rows = srv.device_fetch_rows(np.arange(batch, dtype=np.int32))
    assert rows.shape == (batch, COLS)
    # at the bucket the program's output itself comes back: no slice
    assert (rows is outputs[0]) == (batch == 64)


# -- (d) a run of consecutive rows is read by a slice ------------------------
# The host holds the ids before anything is copied, so it chooses between
# two programs (``_fetch_run``): on one shard, ids strictly consecutive
# whose bucket-long slice stays inside the live rows are read by
# ``ops.slice_rows`` with no ids on the device; anything else gathers, as
# before. Either way the rows are the gather's, bit for bit.

RUN_ROWS = 256      # a rung of the bucket ladder: the whole table is a run


@pytest.fixture()
def one_shard():
    import multiverso_tpu as mv
    mv.MV_Init(["-num_workers=1"], devices=jax.devices()[:1])
    yield mv
    mv.MV_ShutDown()


def _fetch_runs():
    return metrics.snapshot().get(
        "table.device_fetch.dense_runs", {}).get("value", 0)


def _programs_of(srv, monkeypatch):
    """Record which of the two read programs a fetch launches."""
    ran = []
    gather, slice_rows = srv._gather_rows, srv._slice_rows

    def gathering(*args):
        ran.append("gather")
        return gather(*args)

    def slicing(data, **small):
        ran.append("slice")
        return slice_rows(data, **small)
    monkeypatch.setattr(srv, "_gather_rows", gathering)
    monkeypatch.setattr(srv, "_slice_rows", slicing)
    return ran


def _the_gathers_rows(srv, ids):
    rows = srv._gather_rows(srv.state["data"], srv.state["aux"],
                            srv._device_ids(ids))
    return np.asarray(rows)[: len(ids)]


RUNS = {    # ids, and whether the slice program reads them
    "from_row_0": (np.arange(0, 64), True),
    "from_the_middle": (np.arange(40, 104), True),
    "to_the_last_live_row": (np.arange(RUN_ROWS - 64, RUN_ROWS), True),
    "shorter_than_its_bucket": (np.arange(10, 50), True),
    "one_row": (np.arange(7, 8), True),
    "the_whole_table": (np.arange(RUN_ROWS), True),
    "most_of_the_table": (np.arange(RUN_ROWS - 30), True),
    # 40 ids from row 200: the 64 lanes of their bucket would pass row 255
    "bucket_past_the_live_rows": (np.arange(200, 240), False),
    "whole_bucket_not_from_row_0": (np.arange(5, RUN_ROWS - 20), False),
    "a_gap": (np.delete(np.arange(40, 105), 30), False),
    "a_repeat": (np.sort(np.append(np.arange(40, 103), 70)), False),
    # ends as far apart as a run's, a repeat and a gap between them
    "a_repeat_and_a_gap": (np.array([3, 4, 4, 6]), False),
    "descending": (np.arange(103, 39, -1), False),
    "shuffled": (np.random.default_rng(5).permutation(np.arange(40, 104)),
                 False),
}


@pytest.mark.parametrize("cols", [256, 50], ids=["two_tiles", "50_cols"])
@pytest.mark.parametrize("case", list(RUNS))
def test_fetch_of_a_run_is_a_slice_and_equals_the_gather(
        one_shard, monkeypatch, case, cols):
    ids, sliced = RUNS[case]
    ids = ids.astype(np.int32)
    init = np.random.default_rng(1).standard_normal(
        (RUN_ROWS, cols)).astype(np.float32)
    srv = _table(one_shard, "adagrad", init, RUN_ROWS, cols).server()
    assert srv.num_servers == 1 and srv.block_rows == RUN_ROWS
    assert srv.store_cols == (256 if cols == 256 else 128)  # the pad is cut
    want = _the_gathers_rows(srv, ids)
    ran, before = _programs_of(srv, monkeypatch), _fetch_runs()
    rows = srv.device_fetch_rows(ids)
    assert isinstance(rows, jax.Array) and rows.shape == (len(ids), cols)
    assert ran == ["slice" if sliced else "gather"]
    assert _fetch_runs() - before == (1 if sliced else 0)
    np.testing.assert_array_equal(np.asarray(rows), want)
    np.testing.assert_array_equal(want, init[ids])


@pytest.mark.parametrize("case", ["the_whole_table", "most_of_the_table",
                                  "shorter_than_its_bucket"])
def test_slice_program_hands_back_the_gathers_bucket(one_shard, case):
    """The program's own output, pad lanes included: zero at and past the
    run's end, as the gather's mask leaves them."""
    ids = RUNS[case][0].astype(np.int32)
    init = np.random.default_rng(2).standard_normal(
        (RUN_ROWS, 50)).astype(np.float32) + 3.0
    srv = _table(one_shard, "default", init, RUN_ROWS, 50).server()
    run = srv._fetch_run(ids)
    assert (run["count"] is None) == (len(ids) == next_bucket(len(ids)))
    got = srv._slice_rows(srv.state["data"], **run)
    want = srv._gather_rows(srv.state["data"], srv.state["aux"],
                            srv._device_ids(ids))
    assert got.shape == want.shape == (next_bucket(len(ids)), 50)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_one_row_tables_repeated_id_takes_the_gather(one_shard, monkeypatch):
    """``rec_bag_steps``' smallest table: 2,048 positions, all of them row
    0. The ends are equal, the length is not 1: not a run."""
    init = np.full((1, 128), 2.5, np.float32)
    srv = _table(one_shard, "adagrad", init, 1, 128).server()
    ran, before = _programs_of(srv, monkeypatch), _fetch_runs()
    rows = srv.device_fetch_rows(np.zeros(2048, np.int32))
    assert ran == ["gather"] and _fetch_runs() == before
    np.testing.assert_array_equal(np.asarray(rows), 2.5)
    # its one row alone is a run of one, inside the one live row
    assert srv._fetch_run(np.zeros(1, np.int32)) is None    # bucket 8 > 1


@pytest.mark.parametrize("updater", ["default", "adagrad"])
def test_fetch_after_an_apply_of_the_same_run_returns_the_applied_rows(
        one_shard, monkeypatch, updater):
    """``lm_vocab_steps``' step: fetch a run, apply a delta made on the
    device to the same run, fetch again. Held to the host plane on a twin
    table, bit for bit (whole-number deltas)."""
    rng = np.random.default_rng(4)
    init = rng.integers(-8, 9, (RUN_ROWS, 256)).astype(np.float32)
    dev, host = (_table(one_shard, updater, init, RUN_ROWS, 256)
                 for _ in range(2))
    srv = dev.server()
    ran = _programs_of(srv, monkeypatch)
    for ids in (np.arange(RUN_ROWS, dtype=np.int32),
                np.arange(32, 96, dtype=np.int32)):
        for _ in range(2):
            delta = rng.integers(-3, 4, (len(ids), 256)).astype(np.float32)
            srv.device_apply_rows(ids, jnp.asarray(delta))
            host.AddRows(ids, delta)
            np.testing.assert_array_equal(
                np.asarray(srv.device_fetch_rows(ids)), host.GetRows(ids))
    assert ran == ["slice"] * 4


def test_four_shards_take_the_gather(monkeypatch):
    """A shard sees the middle of a cross-shard run: the static one-shard
    guard keeps ``tables_rounds_4c`` on the ``shard_map`` gather."""
    import multiverso_tpu as mv
    mv.MV_Init(["-num_workers=1"], devices=jax.devices()[:4])
    try:
        init = np.random.default_rng(6).standard_normal(
            (RUN_ROWS, 128)).astype(np.float32)
        srv = _table(mv, "adagrad", init, RUN_ROWS, 128).server()
        assert srv.num_servers == 4
        ran, before = _programs_of(srv, monkeypatch), _fetch_runs()
        for ids in (np.arange(RUN_ROWS), np.arange(8, 16)):   # one shard's
            rows = srv.device_fetch_rows(ids.astype(np.int32))
            np.testing.assert_array_equal(np.asarray(rows), init[ids])
        assert ran == ["gather", "gather"] and _fetch_runs() == before
    finally:
        mv.MV_ShutDown()


def test_an_access_hook_keeps_the_gather(monkeypatch):
    """The gather's program applies an updater's ``access`` hook to the
    rows it reads; the slice program does not, so such a table gathers."""
    import multiverso_tpu as mv
    from multiverso_tpu.updaters import base

    class Doubled(base.AddUpdater):
        def access(self, data, aux, opt):
            return data * 2

    monkeypatch.setitem(base._REGISTRY, "doubled", Doubled)
    mv.MV_Init(["-num_workers=1"], devices=jax.devices()[:1])
    try:
        init = np.arange(RUN_ROWS * 128, dtype=np.float32).reshape(-1, 128)
        srv = _table(mv, "doubled", init, RUN_ROWS, 128).server()
        ran, before = _programs_of(srv, monkeypatch), _fetch_runs()
        ids = np.arange(64, dtype=np.int32)
        np.testing.assert_array_equal(
            np.asarray(srv.device_fetch_rows(ids)), 2 * init[ids])
        assert ran == ["gather"] and _fetch_runs() == before
    finally:
        mv.MV_ShutDown()


def test_a_run_copies_no_ids_and_is_one_call(one_shard):
    """The crossings of a fetch (``tables/crossing.py``): a gathered set
    is one copy of its padded ids and one call; a run copies nothing and
    is one call, two when the run is shorter than its bucket (the cut)."""
    srv = _table(one_shard, rows=RUN_ROWS).server()

    def crossings(ids):
        def read():
            snap = metrics.snapshot()
            return [snap.get(f"table.device.{k}", {}).get("value", 0)
                    for k in ("h2d_copies", "h2d_bytes", "calls")]
        before = read()
        srv.device_fetch_rows(np.asarray(ids, np.int32))
        return [a - b for a, b in zip(read(), before)]
    assert crossings(np.arange(64)[::-1]) == [1, 4 * 64, 1]
    assert crossings(np.arange(64)) == [0, 0, 1]
    assert crossings(np.arange(40)) == [0, 0, 2]
