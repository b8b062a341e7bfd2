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
