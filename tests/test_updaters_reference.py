"""A MatrixTable under each stateful server-side updater against the plain
reference (multiverso_tpu/updaters/reference.py), through the device
plane: ``device_fetch_rows`` / ``device_apply_rows`` on seeded random rows,
at one 128-lane tile, two, and the 2,048 columns of a language model's
vocabulary tables, for id sets that are distinct, that repeat 60 % of
their positions (an embedding gradient has one row per token position),
and that name the whole table in order (an output head under a full
softmax).

Tolerance: both sides compute in float32, and differ only in the order
repeated deltas are summed in and in how the compiler rounds ``delta /
lr``, ``1 / sqrt`` and a fused multiply-add, a few units in the last place
of a step. ``RTOL`` 2e-5 of the entry plus ``ATOL`` 2e-6 carries three
rounds of that (measured on this CPU: rows differ by 3e-8 at worst,
history by 1.7e-5 of an entry). It is far inside what a wrong result
gives: an unsummed repeat or a dropped round changes an entry by a whole
step (AdaGrad: rho / sqrt(t)), rows or history kept in bfloat16 by 2**-9
of an entry.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.parallel.mesh import next_bucket
from multiverso_tpu.tables import MatrixTableOption
from multiverso_tpu.telemetry import metrics
from multiverso_tpu.updaters import reference
from multiverso_tpu.updaters.base import AddOption

ROWS = 96
RTOL, ATOL = 2e-5, 2e-6
OPTION = dict(worker_id=1, momentum=0.9, learning_rate=0.02, rho=0.05,
              lambda_=0.2)


def _id_sets(kind: str, rng) -> list:
    """Three rounds of row ids of one kind."""
    if kind == "distinct":
        return [rng.choice(ROWS, 40, replace=False).astype(np.int32)
                for _ in range(3)]
    if kind == "repeated":      # 40 positions, 24 of them (60 %) repeats
        return [rng.permutation(np.concatenate(
            [u, rng.choice(u, 24)])).astype(np.int32)
            for u in (rng.choice(ROWS, 16, replace=False)
                      for _ in range(3))]
    assert kind == "whole"
    return [np.arange(ROWS, dtype=np.int32)] * 3


def _state_leaf(srv, name):
    """A per-worker or shared aux leaf in the logical row layout."""
    return srv.aux_to_logical(name, srv.state["aux"][name])


@pytest.fixture()
def world():
    import multiverso_tpu as mv
    mv.MV_Init(["-num_workers=2"])
    yield mv
    mv.MV_ShutDown()


@pytest.mark.parametrize("kind", ["distinct", "repeated", "whole"])
@pytest.mark.parametrize("cols", [128, 256, 2048])
@pytest.mark.parametrize("updater", ["momentum", "adagrad", "dcasgd"])
def test_table_equals_reference(world, updater, cols, kind):
    rng = np.random.default_rng(cols * 7 + len(kind) + len(updater))
    init = (0.02 * rng.standard_normal((ROWS, cols))).astype(np.float32)
    table = world.MV_CreateTable(MatrixTableOption(
        num_rows=ROWS, num_cols=cols, updater_type=updater,
        initializer=lambda shape: init))
    srv = table.server()
    want = reference.new_state(init, updater, num_workers=2)
    for ids in _id_sets(kind, rng):
        fetched = srv.device_fetch_rows(ids)
        assert isinstance(fetched, jax.Array)
        np.testing.assert_allclose(np.asarray(fetched), want["data"][ids],
                                   rtol=RTOL, atol=ATOL)
        # a delta of the size of lr * g, made on the device
        delta = jnp.asarray((1e-3 * rng.standard_normal(
            (len(ids), cols))).astype(np.float32))
        srv.device_apply_rows(ids, delta, AddOption(**OPTION))
        reference.apply_rows(updater, want, ids, np.asarray(delta), **OPTION)
    np.testing.assert_allclose(srv.raw(), want["data"], rtol=RTOL, atol=ATOL)
    for name in ("smooth", "hist", "backup"):
        if name in want:
            np.testing.assert_allclose(_state_leaf(srv, name), want[name],
                                       rtol=RTOL, atol=ATOL)


def _repeated_batch(cols=256):
    rng = np.random.default_rng(5)
    ids = np.array([3, 9, 3, 40, 9, 3, 77, 40], np.int32)
    delta = (1e-3 * rng.standard_normal((len(ids), cols))).astype(np.float32)
    init = (0.02 * rng.standard_normal((ROWS, cols))).astype(np.float32)
    return ids, delta, init


def test_device_delta_with_repeats_never_leaves_the_device(world,
                                                           monkeypatch):
    """Repeated ids under a device-resident delta combine on the device:
    no device-to-host copy is allowed while the verb runs (the guard is
    real on an accelerator; the CPU backend hands numpy its buffer without
    a transfer, so here the host combine is also made to fail), and the
    counter of such copies stays at 0."""
    ids, delta, init = _repeated_batch()
    table = world.MV_CreateTable(MatrixTableOption(
        num_rows=ROWS, num_cols=256, updater_type="adagrad",
        initializer=lambda shape: init))
    srv = table.server()
    on_device = jnp.asarray(delta)

    def no_host_combine(*args):
        raise AssertionError("a device-resident delta took the host combine")
    monkeypatch.setattr(srv, "_combine_duplicates", no_host_combine)
    before = metrics.snapshot()
    with jax.transfer_guard_device_to_host("disallow"):
        srv.device_apply_rows(ids, on_device)
        jax.block_until_ready(srv.state)
    after = metrics.snapshot()
    moved = lambda name: (after[name]["value"]  # noqa: E731
                          - before.get(name, {}).get("value", 0.0))
    assert after["table.device_apply.d2h_bytes"]["value"] == 0
    assert moved("table.device_apply.rows") == len(ids)
    assert moved("table.device_apply.unique_rows") == 4
    assert moved("table.device_apply.bytes") == len(ids) * 256 * 4
    want = reference.apply_rows(
        "adagrad", reference.new_state(init, "adagrad", 2), ids, delta)
    np.testing.assert_allclose(srv.raw(), want["data"], rtol=RTOL, atol=ATOL)


def test_host_delta_with_repeats_takes_the_host_combine(world, monkeypatch):
    ids, delta, init = _repeated_batch()
    table = world.MV_CreateTable(MatrixTableOption(
        num_rows=ROWS, num_cols=256, updater_type="adagrad",
        initializer=lambda shape: init))
    srv = table.server()
    seen = []
    combine = srv._combine_duplicates
    monkeypatch.setattr(srv, "_combine_duplicates",
                        lambda i, d: seen.append(len(i)) or combine(i, d))
    srv.device_apply_rows(ids, delta)
    assert seen == [len(ids)]
    want = reference.apply_rows(
        "adagrad", reference.new_state(init, "adagrad", 2), ids, delta)
    np.testing.assert_allclose(srv.raw(), want["data"], rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(_state_leaf(srv, "hist"), want["hist"],
                               rtol=RTOL, atol=ATOL)


# -- per-worker state, workers interleaved (ISSUE 29) -----------------------
# Three workers' Adds in the order 0, 2, 1, 0 through every path that
# reaches a stateful updater. Per-worker state is row-shaped storage in
# which a shard stacks its workers' blocks (updaters/base.py worker_rows):
# an Add reads and writes the rows of the worker that sent it, whose id is
# a TRACED scalar of the one compiled program.

_COMPILES = []
jax.monitoring.register_event_duration_secs_listener(
    lambda name, secs, **kw: _COMPILES.append(name)
    if name == "/jax/core/compile/backend_compile_duration" else None)

WORKER_ORDER = (0, 2, 1, 0)
PATHS = ["device_distinct", "device_repeated", "device_whole",
         "device_whole_dense_run", "host_add_rows", "whole_add", "array"]


def _apply(path, table, ids, delta, option):
    srv = table.server()
    if path.startswith("device"):
        srv.device_apply_rows(ids, jnp.asarray(delta), option)
    elif path == "host_add_rows":
        table.AddRows(ids, delta, option)
    else:                       # whole_add and array: every row, in order
        table.Add(delta, option)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("updater", ["adagrad", "dcasgd"])
def test_workers_interleaved(updater, path, monkeypatch):
    import multiverso_tpu as mv
    from multiverso_tpu.ops import rows as ops_rows
    from multiverso_tpu.tables import ArrayTableOption
    cols = 1 if path == "array" else 128
    # the dense run (a slice for consecutive ids) belongs to one shard on
    # the chip; here it runs on one CPU device, which copies where the
    # chip aliases and computes the same
    one_shard = path == "device_whole_dense_run"
    if one_shard:
        monkeypatch.setattr(ops_rows, "_dense_backend_ok", lambda: True)
    kind = {"device_distinct": "distinct", "device_repeated": "repeated",
            "host_add_rows": "repeated"}.get(path, "whole")
    rng = np.random.default_rng(len(path) * 31 + len(updater))
    init = (0.02 * rng.standard_normal((ROWS, cols))).astype(np.float32)
    mv.MV_Init(["-num_workers=3"],
               devices=jax.devices()[:1] if one_shard else None)
    try:
        if path == "array":
            table = mv.MV_CreateTable(ArrayTableOption(
                size=ROWS, updater_type=updater))
            want = reference.new_state(np.zeros_like(init), updater, 3)
        else:
            table = mv.MV_CreateTable(MatrixTableOption(
                num_rows=ROWS, num_cols=cols, updater_type=updater,
                initializer=lambda shape: init))
            want = reference.new_state(init, updater, 3)
        srv = table.server()
        name = "hist" if updater == "adagrad" else "backup"
        for leaf in jax.tree.leaves(srv.state["aux"]):
            assert leaf.ndim == srv.state["data"].ndim   # no (W, rows, cols)
        id_sets = _id_sets(kind, rng) + _id_sets(kind, rng)
        compiles, start = None, len(_COMPILES)
        for wid, ids in zip(WORKER_ORDER, id_sets):
            delta = (1e-3 * rng.standard_normal((len(ids), cols))
                     ).astype(np.float32)
            option = AddOption(**dict(OPTION, worker_id=wid))
            before = _state_leaf(srv, name)
            _apply(path, table, ids,
                   delta.ravel() if path == "array" else delta, option)
            jax.block_until_ready(srv.state)
            reference.apply_rows(updater, want, ids, delta,
                                 **dict(OPTION, worker_id=wid))
            after = _state_leaf(srv, name)
            assert after.shape == (3,) + before.shape[1:]
            others = [w for w in range(3) if w != wid]
            # bit for bit: another worker's Add is no event for a worker
            np.testing.assert_array_equal(after[others], before[others])
            assert not np.array_equal(after[wid], before[wid])
            if compiles is None:
                compiles = len(_COMPILES)   # worker 0 compiled the programs
                assert compiles > start
        # a change of worker compiled nothing
        assert len(_COMPILES) == compiles
        data = table.Get() if path == "array" else srv.raw()
        np.testing.assert_allclose(
            np.asarray(data).reshape(ROWS, cols), want["data"],
            rtol=RTOL, atol=ATOL)
        np.testing.assert_allclose(
            _state_leaf(srv, name).reshape(want[name].shape), want[name],
            rtol=RTOL, atol=ATOL)
    finally:
        mv.MV_ShutDown()


# -- the stateful apply's dense run (ISSUE 32) -------------------------------
# ops.update_rows_with_state decides the dense run once for the rows and
# every state leaf. The chip takes that branch; here the backend test is
# patched so that one CPU device takes it too, and the same Adds through
# the general branch (gather, update, scatter) must give the same bits.

DENSE_ROWS = 128        # buckets 64 and 128 fit inside the 129 stored rows
DENSE_CASES = {         # ids of every Add, and whether the run is dense
    "whole_table": (np.arange(DENSE_ROWS), True),
    "starts_past_row_0": (np.arange(40, 104), True),
    # 40 ids in a bucket of 64: rows 50..73 and their state are pad lanes
    "shorter_than_bucket": (np.arange(10, 50), True),
    "in_order_with_gap": (np.r_[10:30, 31:51], False),
    "shuffled": (np.random.default_rng(3).permutation(np.arange(10, 74)),
                 False),
}


# momentum's ``m * smooth + (1 - m) * delta`` is a multiply-add that the CPU's
# compiler contracts in one branch's loop and not in the other's (an ulp);
# with m = 0.5 and values on a binary grid every step is exact, so the
# comparison stays bit for bit and a lane that took the wrong value shows
DENSE_OPTION = dict(OPTION, momentum=0.5)


def _on_grid(values, step):
    return (np.round(values / step) * step).astype(np.float32)


def _one_shard_table(updater, dense, monkeypatch, workers=3):
    """A world of one CPU device (MV_ShutDown is the caller's) whose row
    programs hold the dense-run ``cond`` or do not, and a primed table."""
    import multiverso_tpu as mv
    from multiverso_tpu.ops import rows as ops_rows
    monkeypatch.setattr(ops_rows, "_dense_backend_ok", lambda: dense)
    rng = np.random.default_rng(11)
    init = _on_grid(0.02 * rng.standard_normal((DENSE_ROWS, 128)), 2.0 ** -16)
    mv.MV_Init([f"-num_workers={workers}"], devices=jax.devices()[:1])
    table = mv.MV_CreateTable(MatrixTableOption(
        num_rows=DENSE_ROWS, num_cols=128, updater_type=updater,
        initializer=lambda shape: init))
    # every row's state away from zero before the Adds under test (the
    # whole-table program holds no cond): a pad lane that decayed its
    # momentum or refreshed its backup would show
    table.Add(_on_grid(1e-3 * rng.standard_normal(init.shape), 2.0 ** -12),
              AddOption(**DENSE_OPTION))
    return table.server()


def _dense_runs_counted():
    return metrics.snapshot().get(
        "table.device_apply.dense_runs", {}).get("value", 0)


def _states_after_adds(updater, ids, dense, monkeypatch):
    """Storage (rows and every state leaf, trash rows included) after each
    of four Adds of ``ids`` from workers 0, 2, 1, 0."""
    import multiverso_tpu as mv
    from multiverso_tpu.ops import rows as ops_rows
    ids = np.asarray(ids, np.int32)
    rng = np.random.default_rng(17)
    seen = []
    try:
        srv = _one_shard_table(updater, dense, monkeypatch)
        if dense:   # the static guards pass: the program holds the cond
            assert next_bucket(len(ids)) < srv.state["data"].shape[0]
        safe = jnp.asarray(srv.pad_ids(ids))
        safe = jnp.where(safe >= 0, safe, srv.block_rows)
        run = bool(ops_rows._dense_run(safe, srv.shard_rows)[0])
        before = _dense_runs_counted()
        for wid in WORKER_ORDER:
            delta = _on_grid(1e-3 * rng.standard_normal((len(ids), 128)),
                             2.0 ** -12)
            srv.device_apply_rows(
                ids, jnp.asarray(delta),
                AddOption(**dict(DENSE_OPTION, worker_id=wid)))
            seen.append(jax.tree.map(np.asarray, srv.state))
        counted = _dense_runs_counted() - before
    finally:
        mv.MV_ShutDown()
    return seen, run, counted


@pytest.mark.parametrize("case", list(DENSE_CASES))
@pytest.mark.parametrize("updater", ["adagrad", "dcasgd", "momentum"])
def test_dense_branch_equals_general_branch(updater, case, monkeypatch):
    ids, is_run = DENSE_CASES[case]
    dense, run, counted = _states_after_adds(updater, ids, True, monkeypatch)
    general, _, _ = _states_after_adds(updater, ids, False, monkeypatch)
    # the device's own test and the host's counter agree on the case
    assert run == is_run
    assert counted == (len(WORKER_ORDER) if is_run else 0)
    for a, b in zip(dense, general):
        # rows, the sending worker's state, every other worker's: bit for bit
        jax.tree.map(np.testing.assert_array_equal, a, b)
    # and an Add changed what it named and nothing after the run's end
    named = np.zeros(DENSE_ROWS + 1, bool)
    named[ids] = True
    for prev, cur in zip(dense, dense[1:]):
        changed = np.any(prev["data"] != cur["data"], axis=1)
        assert changed[named].all() and not changed[~named].any()


def _equations(jaxpr):
    """Every equation of a jaxpr and of everything it calls."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("updater", ["adagrad", "dcasgd", "momentum"])
def test_stateful_row_program_has_one_cond_and_a_slice_a_table(
        updater, monkeypatch):
    """The row program of a table with updater state holds ONE ``cond``
    (not one a leaf), and its dense branch reads each table with one
    slice and writes it with one update-slice: no gather, no scatter."""
    import multiverso_tpu as mv
    try:
        srv = _one_shard_table(updater, True, monkeypatch)
        ids = jnp.asarray(srv.pad_ids(np.arange(10, 50, dtype=np.int32)))
        program = jax.make_jaxpr(srv.device_update_rows)(
            srv.state, ids, jnp.zeros((64, 128), jnp.float32),
            AddOption().as_jnp())
    finally:
        mv.MV_ShutDown()
    conds = [e for e in _equations(program.jaxpr)
             if e.primitive.name == "cond"]
    assert len(conds) == 1
    general, dense = (
        collections.Counter(e.primitive.name for e in _equations(b.jaxpr))
        for b in conds[0].params["branches"])
    tables = 1 + len(jax.tree.leaves(srv.state["aux"]))
    assert tables == 2
    assert dense["gather"] == 0 and dense["scatter"] == 0
    assert dense["dynamic_slice"] == tables
    assert dense["dynamic_update_slice"] == tables
    assert general["gather"] == tables and general["scatter"] == tables
    assert general["dynamic_update_slice"] == 0


@pytest.mark.parametrize("kind,stepped", [
    ("whole_in_order", 1), ("shuffled", 0), ("repeated", 0), ("sharded", 0)])
def test_dense_runs_counter(kind, stepped):
    """``table.device_apply.dense_runs`` counts the batches the device's
    dense-run test will accept, as far as the host can see them."""
    import multiverso_tpu as mv
    ids = np.arange(DENSE_ROWS, dtype=np.int32)
    if kind == "shuffled":
        ids = np.random.default_rng(2).permutation(ids).astype(np.int32)
    elif kind == "repeated":
        ids = np.sort(np.concatenate([ids, ids[:8]]))
    mv.MV_Init(["-num_workers=1"],
               devices=None if kind == "sharded" else jax.devices()[:1])
    try:
        srv = mv.MV_CreateTable(MatrixTableOption(
            num_rows=DENSE_ROWS, num_cols=128,
            updater_type="adagrad")).server()
        assert (srv.num_servers > 1) == (kind == "sharded")
        before = _dense_runs_counted()
        srv.device_apply_rows(ids, jnp.ones((len(ids), 128), jnp.float32))
        assert _dense_runs_counted() - before == stepped
    finally:
        mv.MV_ShutDown()
