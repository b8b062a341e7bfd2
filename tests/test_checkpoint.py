"""Framework-level checkpoint/resume (multiverso_tpu/checkpoint.py).

The reference only has per-table, app-initiated, data-only Store/Load
(table_interface.h:61-70); these tests cover the driver that the TPU build
adds per SURVEY.md §5: all tables in one call, updater aux state included,
resume exactness across a simulated restart.
"""

import os

import numpy as np
import pytest


@pytest.fixture()
def ckpt_path(tmp_path):
    return str(tmp_path / "state.mvt")


class TestCheckpointDriver:
    def test_save_load_roundtrip_all_tables(self, mv_env, ckpt_path):
        from multiverso_tpu.tables import (ArrayTableOption, KVTableOption,
                                           MatrixTableOption)
        arr = mv_env.MV_CreateTable(ArrayTableOption(size=40))
        mat = mv_env.MV_CreateTable(MatrixTableOption(num_rows=16, num_cols=8))
        kv = mv_env.MV_CreateTable(KVTableOption())
        arr.Add(np.arange(40, dtype=np.float32))
        mat.AddRows(np.array([1, 5], np.int32), np.ones((2, 8), np.float32))
        kv.Add(np.array([7, 9], np.int64), np.array([1.5, 2.5], np.float32))

        assert mv_env.MV_SaveCheckpoint(ckpt_path) == 3

        # mutate everything, then restore
        arr.Add(np.full(40, 100.0, np.float32))
        mat.AddRows(np.array([1], np.int32), np.full((1, 8), 7.0, np.float32))
        kv.Add(np.array([7], np.int64), np.array([50.0], np.float32))

        assert mv_env.MV_LoadCheckpoint(ckpt_path) == 3
        np.testing.assert_allclose(arr.Get(), np.arange(40, dtype=np.float32))
        got = mat.GetRows(np.array([1, 5], np.int32))
        np.testing.assert_allclose(got, 1.0)
        np.testing.assert_allclose(kv.Get(np.array([7, 9], np.int64)),
                                   [1.5, 2.5])

    def test_checkpoint_over_remote_scheme(self, mv_env):
        """MV_SaveCheckpoint/MV_LoadCheckpoint over a remote stream scheme
        (fsspec memory:// fake backend — the same path gs://hdfs://s3://
        take once -use_remote_io opens the MULTIVERSO_USE_HDFS-style
        gate). Checkpointing is the recovery story; it must reach remote
        storage like the reference's HDFS build did."""
        from multiverso_tpu.tables import ArrayTableOption
        from multiverso_tpu.utils.configure import SetCMDFlag
        SetCMDFlag("use_remote_io", True)
        try:
            arr = mv_env.MV_CreateTable(ArrayTableOption(size=12))
            arr.Add(np.arange(12, dtype=np.float32))
            uri = "memory://ckpts/state.mvt"
            assert mv_env.MV_SaveCheckpoint(uri) == 1
            arr.Add(np.full(12, 9.0, np.float32))
            assert mv_env.MV_LoadCheckpoint(uri) == 1
            np.testing.assert_allclose(arr.Get(),
                                       np.arange(12, dtype=np.float32))
        finally:
            SetCMDFlag("use_remote_io", False)

    def test_adagrad_aux_survives_resume(self, mv_env, ckpt_path):
        """Resume is exact: the per-worker AdaGrad history is restored, so a
        post-resume Add produces the same result as an uninterrupted run
        (the reference loses this state — SURVEY.md §5)."""
        from multiverso_tpu.tables import MatrixTableOption
        from multiverso_tpu.updaters import AddOption

        def run(interrupt):
            t = mv_env.MV_CreateTable(MatrixTableOption(
                num_rows=8, num_cols=4, updater_type="adagrad"))
            opt = AddOption(worker_id=0, learning_rate=0.1, rho=0.5)
            ids = np.array([2, 3], np.int32)
            t.AddRows(ids, np.ones((2, 4), np.float32), option=opt)
            if interrupt:
                mv_env.MV_SaveCheckpoint(ckpt_path)
                # clobber both data and aux, then restore
                t.AddRows(ids, np.full((2, 4), 9.0, np.float32), option=opt)
                mv_env.MV_LoadCheckpoint(ckpt_path)
            t.AddRows(ids, np.ones((2, 4), np.float32), option=opt)
            return t.GetRows(ids)

        uninterrupted = run(interrupt=False)
        # fresh world for the resumed run
        mv_env.MV_ShutDown()
        mv_env.MV_Init([])
        resumed = run(interrupt=True)
        np.testing.assert_allclose(resumed, uninterrupted, rtol=1e-6)

    def test_save_drains_in_flight_async_adds(self, mv_env, ckpt_path):
        """Fire-and-forget pushes enqueued before the save must be in the
        checkpoint: save_checkpoint drains the engine mailbox first
        (checkpoint._quiesce; native ServerC kRequestBarrier parity)."""
        from multiverso_tpu.tables import ArrayTableOption
        table = mv_env.MV_CreateTable(ArrayTableOption(size=8))
        for _ in range(50):
            table.AddFireForget(np.ones(8, np.float32))
        mv_env.MV_SaveCheckpoint(ckpt_path)
        table.Add(np.full(8, 100.0, np.float32))  # diverge post-save
        mv_env.MV_LoadCheckpoint(ckpt_path)
        np.testing.assert_allclose(table.Get(), 50.0)

    def test_type_mismatch_rejected(self, mv_env, ckpt_path, tmp_path):
        from multiverso_tpu.tables import ArrayTableOption, MatrixTableOption
        from multiverso_tpu.utils.log import FatalError
        mv_env.MV_CreateTable(ArrayTableOption(size=8))
        mv_env.MV_SaveCheckpoint(ckpt_path)
        mv_env.MV_ShutDown()
        mv_env.MV_Init([])
        mv_env.MV_CreateTable(MatrixTableOption(num_rows=2, num_cols=4))
        with pytest.raises(FatalError):
            mv_env.MV_LoadCheckpoint(ckpt_path)

    def test_table_count_mismatch_rejected(self, mv_env, ckpt_path):
        from multiverso_tpu.tables import ArrayTableOption
        from multiverso_tpu.utils.log import FatalError
        mv_env.MV_CreateTable(ArrayTableOption(size=8))
        mv_env.MV_SaveCheckpoint(ckpt_path)
        mv_env.MV_CreateTable(ArrayTableOption(size=8))
        with pytest.raises(FatalError):
            mv_env.MV_LoadCheckpoint(ckpt_path)

    def test_resume_on_different_mesh_size(self, ckpt_path):
        """Layout independence: save on a 4-device mesh, resume on 8 —
        data AND AdaGrad aux must survive exactly (checkpoint.py serializes
        logical layout; the reference's per-server shard files cannot do
        this)."""
        import jax
        import multiverso_tpu as mv
        from multiverso_tpu.tables import MatrixTableOption
        from multiverso_tpu.updaters import AddOption

        opt = AddOption(worker_id=0, learning_rate=0.1, rho=0.5)
        ids = np.array([0, 5, 11], np.int32)

        mv.MV_Init([], devices=jax.devices()[:4])
        t = mv.MV_CreateTable(MatrixTableOption(num_rows=12, num_cols=4,
                                                updater_type="adagrad"))
        t.AddRows(ids, np.ones((3, 4), np.float32), option=opt)
        mv.MV_SaveCheckpoint(ckpt_path)
        expected_next = None
        t.AddRows(ids, np.ones((3, 4), np.float32), option=opt)
        expected_next = t.GetRows(ids).copy()
        mv.MV_ShutDown()

        mv.MV_Init([], devices=jax.devices()[:8])
        t = mv.MV_CreateTable(MatrixTableOption(num_rows=12, num_cols=4,
                                                updater_type="adagrad"))
        mv.MV_LoadCheckpoint(ckpt_path)
        t.AddRows(ids, np.ones((3, 4), np.float32), option=opt)
        resumed_next = t.GetRows(ids)
        np.testing.assert_allclose(resumed_next, expected_next, rtol=1e-6)
        mv.MV_ShutDown()


# A checkpoint written by the tree BEFORE per-worker updater state became
# row-shaped storage (PR 27, 276ca25: leaves (workers, rows, cols) in HBM):
# three MatrixTables 10 x 4 under adagrad, dcasgd and momentum and one
# ArrayTable of 10 under adagrad, -num_workers=3, after the Adds below from
# workers 0, 2, 1. The file holds the LOGICAL form, per-worker state as
# (workers, rows, cols), which no layout change may move.
_PR27_CKPT = os.path.join(os.path.dirname(__file__), "fixtures",
                          "ckpt_pr27_workers3.mvt")
_PR27_OPTION = dict(momentum=0.9, learning_rate=0.02, rho=0.05, lambda_=0.2)


def _pr27_replay():
    """What the fixture's writer did, on the plain reference."""
    from multiverso_tpu.updaters import reference
    rng = np.random.default_rng(29)
    init = (0.02 * rng.standard_normal((10, 4))).astype(np.float32)
    want = {u: reference.new_state(init, u, 3)
            for u in ("adagrad", "dcasgd", "momentum")}
    want["array"] = reference.new_state(np.zeros((10, 1)), "adagrad", 3)
    for wid, ids in ((0, [1, 4, 7]), (2, [4, 5, 9, 0]), (1, [7, 2])):
        delta = (1e-3 * rng.standard_normal((len(ids), 4))).astype(np.float32)
        for u in ("adagrad", "dcasgd", "momentum"):
            reference.apply_rows(u, want[u], ids, delta, worker_id=wid,
                                 **_PR27_OPTION)
        reference.apply_rows(
            "adagrad", want["array"], np.arange(10),
            (1e-3 * rng.standard_normal(10)).astype(np.float32),
            worker_id=wid, **_PR27_OPTION)
    return want


@pytest.mark.parametrize("devices", [1, 2, 8])
def test_checkpoint_of_the_3d_layout_loads_trains_and_stores(devices,
                                                             ckpt_path):
    import jax
    import multiverso_tpu as mv
    from multiverso_tpu.tables import ArrayTableOption, MatrixTableOption
    from multiverso_tpu.updaters import AddOption, reference

    want = _pr27_replay()
    mv.MV_Init(["-num_workers=3"], devices=jax.devices()[:devices])
    try:
        tables = {u: mv.MV_CreateTable(MatrixTableOption(
            num_rows=10, num_cols=4, updater_type=u))
            for u in ("adagrad", "dcasgd", "momentum")}
        tables["array"] = mv.MV_CreateTable(ArrayTableOption(
            size=10, updater_type="adagrad"))
        assert mv.MV_LoadCheckpoint(_PR27_CKPT) == 4
        # the same bytes come back: the logical form did not move
        assert mv.MV_SaveCheckpoint(ckpt_path) == 4
        with open(_PR27_CKPT, "rb") as a, open(ckpt_path, "rb") as b:
            assert a.read() == b.read()

        def check():
            for u, table in tables.items():
                srv = table.server()
                got = table.Get() if u == "array" else srv.raw()
                np.testing.assert_allclose(
                    np.asarray(got).reshape(want[u]["data"].shape),
                    want[u]["data"], rtol=2e-5, atol=2e-6)
                for name, leaf in srv.state["aux"].items():
                    assert leaf.ndim == srv.state["data"].ndim
                    logical = srv.aux_to_logical(name, leaf)
                    assert logical.shape == want[u][name].shape[
                        : logical.ndim]
                    np.testing.assert_allclose(
                        logical.reshape(want[u][name].shape), want[u][name],
                        rtol=2e-5, atol=2e-6)
        check()
        # train a step from worker 1 on the loaded state
        ids = np.array([7, 3, 0], np.int32)
        delta = np.full((3, 4), 2e-3, np.float32)
        opt = dict(_PR27_OPTION, worker_id=1)
        for u in ("adagrad", "dcasgd", "momentum"):
            tables[u].AddRows(ids, delta, AddOption(**opt))
            reference.apply_rows(u, want[u], ids, delta, **opt)
        tables["array"].Add(np.full(10, 2e-3, np.float32), AddOption(**opt))
        reference.apply_rows("adagrad", want["array"], np.arange(10),
                             np.full((10, 1), 2e-3, np.float32), **opt)
        check()
        # ... and a restart from what this tree stores resumes exactly
        assert mv.MV_SaveCheckpoint(ckpt_path) == 4
        for u in ("adagrad", "dcasgd", "momentum"):
            tables[u].AddRows(ids, delta, AddOption(**opt))
        assert mv.MV_LoadCheckpoint(ckpt_path) == 4
        check()
    finally:
        mv.MV_ShutDown()
