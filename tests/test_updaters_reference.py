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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
    return srv.aux_to_logical(np.asarray(srv.state["aux"][name]))


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
