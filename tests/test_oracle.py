"""Property-based oracle tests: random verb sequences vs a numpy model.

The reference's tests hand-pick sequences (Test/unittests); here a seeded
random walk drives the real PS path (worker verbs -> engine -> jit'd
sharded updates on the 8-device mesh) while a plain numpy model applies
the documented semantics; every Get must match the oracle exactly. This
is the cheapest way to catch interaction bugs between padding, bucketing,
trash-row routing, updater state, and duplicate handling.
"""

import numpy as np
import pytest

from multiverso_tpu.tables import (ArrayTableOption, KVTableOption,
                                   MatrixTableOption)
from multiverso_tpu.updaters import AddOption, GetOption


class TestMatrixOracle:
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_random_walk_matches_numpy(self, mv_env, seed):
        rng = np.random.default_rng(seed)
        R, C = int(rng.integers(5, 200)), int(rng.integers(1, 40))
        table = mv_env.MV_CreateTable(MatrixTableOption(num_rows=R,
                                                        num_cols=C))
        oracle = np.zeros((R, C), np.float32)
        for _ in range(40):
            op = rng.integers(0, 4)
            if op == 0:  # whole-table add
                delta = rng.standard_normal((R, C)).astype(np.float32)
                table.Add(delta)
                oracle += delta
            elif op == 1:  # row add, duplicates allowed (they stack)
                k = int(rng.integers(1, R + 1))
                ids = rng.integers(0, R, k).astype(np.int32)
                deltas = rng.standard_normal((k, C)).astype(np.float32)
                table.AddRows(ids, deltas)
                np.add.at(oracle, ids, deltas)
            elif op == 2:  # row get, any order/duplicates
                k = int(rng.integers(1, R + 1))
                ids = rng.integers(0, R, k).astype(np.int32)
                np.testing.assert_allclose(table.GetRows(ids), oracle[ids],
                                           rtol=1e-5, atol=1e-5)
            else:  # whole-table get
                np.testing.assert_allclose(table.Get(), oracle,
                                           rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("updater,seed", [("sgd", 3), ("momentum", 4),
                                              ("adagrad", 5), ("dcasgd", 6)])
    def test_updater_walk_matches_numpy(self, mv_env, updater, seed):
        """Row adds through every updater vs the documented numpy rules
        (updaters/base.py)."""
        rng = np.random.default_rng(seed)
        R, C, W = 37, 5, 3
        import multiverso_tpu as mv
        mv.MV_ShutDown()
        mv.MV_Init([f"-num_workers={W}"])
        try:
            table = mv.MV_CreateTable(MatrixTableOption(
                num_rows=R, num_cols=C, updater_type=updater))
            data = np.zeros((R, C), np.float32)
            smooth = np.zeros((R, C), np.float32)
            hist = np.zeros((W, R, C), np.float32)
            backup = np.zeros((W, R, C), np.float32)
            m, lr, rho, lam = 0.5, 0.1, 0.2, 0.4
            for _ in range(25):
                wid = int(rng.integers(0, W))
                k = int(rng.integers(1, 9))
                ids = rng.choice(R, k, replace=False).astype(np.int32)
                deltas = rng.standard_normal((k, C)).astype(np.float32)
                table.AddRows(ids, deltas, AddOption(
                    worker_id=wid, momentum=m, learning_rate=lr, rho=rho,
                    lambda_=lam))
                if updater == "sgd":
                    data[ids] -= deltas
                elif updater == "momentum":
                    smooth[ids] = m * smooth[ids] + (1 - m) * deltas
                    data[ids] -= smooth[ids]
                elif updater == "adagrad":
                    g = deltas / lr
                    hist[wid][ids] += g * g
                    data[ids] -= rho * g / np.sqrt(hist[wid][ids] + 1e-6)
                else:  # dcasgd
                    comp = deltas + (lam / lr) * deltas * deltas * (
                        data[ids] - backup[wid][ids])
                    data[ids] -= comp
                    backup[wid][ids] = data[ids]
            np.testing.assert_allclose(
                table.GetRows(np.arange(R, dtype=np.int32)), data,
                rtol=2e-4, atol=2e-4)
        finally:
            mv.MV_ShutDown()
            mv.MV_Init([])  # hand mv_env a live world to tear down


class TestMatrixOraclePallas:
    def test_random_walk_through_pallas_kernels(self, mv_env):
        """Same oracle walk with -use_pallas=on, on the chip's own split
        inside the PS path: XLA reads, the interpreter running the Pallas
        write kernel."""
        from multiverso_tpu.utils.configure import SetCMDFlag
        SetCMDFlag("use_pallas", "on")
        try:
            rng = np.random.default_rng(12)
            R, C = 24, 8
            table = mv_env.MV_CreateTable(
                MatrixTableOption(num_rows=R, num_cols=C))
            oracle = np.zeros((R, C), np.float32)
            for _ in range(12):
                k = int(rng.integers(1, R + 1))
                ids = rng.integers(0, R, k).astype(np.int32)
                deltas = rng.standard_normal((k, C)).astype(np.float32)
                table.AddRows(ids, deltas)
                np.add.at(oracle, ids, deltas)
                np.testing.assert_allclose(table.GetRows(ids), oracle[ids],
                                           rtol=1e-5, atol=1e-5)
        finally:
            SetCMDFlag("use_pallas", "auto")


class TestArrayKVOracle:
    @pytest.mark.parametrize("seed", [7, 8])
    def test_array_and_kv_walk(self, mv_env, seed):
        rng = np.random.default_rng(seed)
        N = int(rng.integers(3, 100))
        arr = mv_env.MV_CreateTable(ArrayTableOption(size=N))
        kv = mv_env.MV_CreateTable(KVTableOption())
        a_oracle = np.zeros(N, np.float32)
        kv_oracle = {}
        for _ in range(30):
            op = rng.integers(0, 4)
            if op == 0:
                delta = rng.standard_normal(N).astype(np.float32)
                arr.Add(delta)
                a_oracle += delta
            elif op == 1:
                np.testing.assert_allclose(arr.Get(), a_oracle,
                                           rtol=1e-5, atol=1e-5)
            elif op == 2:
                k = int(rng.integers(1, 20))
                keys = rng.integers(0, 500, k)
                vals = rng.standard_normal(k).astype(np.float32)
                kv.Add(keys, vals)
                for key, v in zip(keys.tolist(), vals.tolist()):
                    kv_oracle[key] = kv_oracle.get(key, 0.0) + v
            else:
                k = int(rng.integers(1, 20))
                keys = rng.integers(0, 500, k)
                expect = np.asarray([kv_oracle.get(int(x), 0.0)
                                     for x in keys], np.float32)
                np.testing.assert_allclose(kv.Get(keys), expect,
                                           rtol=1e-5, atol=1e-5)


class TestRound3Oracle:
    """Random walks over the round-3 surfaces: compressed wires, bursty
    (window-coalesced) pushes, the fused Add+Get round verb, dense runs,
    and host/device plane interleaving — all against the numpy model."""

    @pytest.mark.parametrize("seed", [0, 1])
    def test_compressed_walk_matches_numpy(self, mv_env, seed):
        rng = np.random.default_rng(seed + 40)
        R, C = int(rng.integers(20, 150)), int(rng.integers(2, 24))
        table = mv_env.MV_CreateTable(MatrixTableOption(
            num_rows=R, num_cols=C, compress="sparse"))
        oracle = np.zeros((R, C), np.float32)
        for _ in range(30):
            op = rng.integers(0, 3)
            if op == 0:   # sparse-ish row add (filter engages)
                k = int(rng.integers(1, R + 1))
                ids = rng.integers(0, R, k).astype(np.int32)
                deltas = rng.standard_normal((k, C)).astype(np.float32)
                deltas[rng.random((k, C)) < 0.8] = 0.0
                table.AddRows(ids, deltas)
                np.add.at(oracle, ids, deltas)
            elif op == 1:  # dense row add (filter falls back)
                k = int(rng.integers(1, R + 1))
                ids = rng.integers(0, R, k).astype(np.int32)
                deltas = rng.standard_normal((k, C)).astype(np.float32)
                table.AddRows(ids, deltas)
                np.add.at(oracle, ids, deltas)
            else:
                k = int(rng.integers(1, R + 1))
                ids = rng.integers(0, R, k).astype(np.int32)
                np.testing.assert_allclose(table.GetRows(ids), oracle[ids],
                                           rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(table.Get(), oracle, rtol=1e-4,
                                   atol=1e-5)
        # the compressed wire must actually have engaged (a silent
        # dense-path regression would keep the oracle green)
        assert table.server().wire_stats["payload_bytes"] > 0

    @pytest.mark.parametrize("seed", [0, 1])
    def test_bursty_walk_matches_numpy(self, mv_env, seed):
        """Fire-and-forget bursts force merged windows; interleaved gets
        must observe a PREFIX-consistent state (async contract) and the
        final state must be exact."""
        rng = np.random.default_rng(seed + 50)
        R, C = int(rng.integers(30, 120)), int(rng.integers(1, 16))
        table = mv_env.MV_CreateTable(MatrixTableOption(num_rows=R,
                                                        num_cols=C))
        oracle = np.zeros((R, C), np.float32)
        for _ in range(12):
            burst = int(rng.integers(1, 9))
            for _ in range(burst):
                k = int(rng.integers(1, R + 1))
                ids = rng.integers(0, R, k).astype(np.int32)
                deltas = rng.standard_normal((k, C)).astype(np.float32)
                table.AddFireForget(deltas, row_ids=ids)
                np.add.at(oracle, ids, deltas)
            # a tracked Get after the burst sees ALL of it (same-table
            # FIFO: the engine's window applies queued adds first)
            np.testing.assert_allclose(
                table.GetRows(np.arange(R, dtype=np.int32)), oracle,
                rtol=1e-4, atol=1e-5)

    def test_fused_round_walk_matches_numpy(self, mv_env):
        import jax
        import jax.numpy as jnp
        rng = np.random.default_rng(7)
        R, C = 64, 8
        table = mv_env.MV_CreateTable(MatrixTableOption(num_rows=R,
                                                        num_cols=C))
        srv = table.server()
        oracle = np.zeros((R, C), np.float32)
        opt = AddOption().as_jnp()
        fused = jax.jit(srv.device_update_gather_rows)
        for i in range(10):
            if i % 3 == 0:   # dense contiguous run (fast-path shape)
                start = int(rng.integers(0, R - 8))
                ids = (np.arange(8) + start).astype(np.int32)
            else:
                ids = np.sort(rng.choice(R, 8, replace=False)).astype(
                    np.int32)
            deltas = rng.standard_normal((8, C)).astype(np.float32)
            padded = srv.pad_ids(ids)
            pd = np.zeros((len(padded), C), np.float32)
            pd[:8] = deltas
            state, rows = fused(srv.state, jnp.asarray(padded),
                                jnp.asarray(pd), opt)
            srv.state = state
            np.add.at(oracle, ids, deltas)
            # the Get half returns POST-update rows
            np.testing.assert_allclose(np.asarray(rows)[:8], oracle[ids],
                                       rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(table.Get(), oracle, rtol=1e-4,
                                   atol=1e-5)


class TestRound4Oracle:
    """Random walks over the round-4 surfaces: the host verbs
    interleaved with every other plane, and the LR device-plane window
    programs — all against numpy models."""

    @pytest.mark.parametrize("seed", [0, 1])
    def test_planes_interleaved_walk_matches_numpy(self, mv_env, seed):
        """Host verbs, device verbs, engine bursts, and Store/Load
        interleave randomly over the one ``state``; every read and the
        final state must match the numpy oracle."""
        import io as _io
        from multiverso_tpu.utils.io import Stream
        from multiverso_tpu.zoo import Zoo
        rng = np.random.default_rng(seed + 60)
        R, C = int(rng.integers(24, 100)), int(rng.integers(2, 12))
        table = mv_env.MV_CreateTable(MatrixTableOption(num_rows=R,
                                                        num_cols=C))
        srv = table.server()
        oracle = np.zeros((R, C), np.float32)
        snapshot = None
        for _ in range(40):
            op = rng.integers(0, 6)
            k = int(rng.integers(1, R + 1))
            ids = np.unique(rng.integers(0, R, k)).astype(np.int32)
            if op == 0:     # host add
                d = rng.standard_normal((len(ids), C)).astype(np.float32)
                table.AddRows(ids, d)
                np.add.at(oracle, ids, d)
            elif op == 1:   # host get
                np.testing.assert_allclose(table.GetRows(ids), oracle[ids],
                                           rtol=1e-4, atol=1e-5)
            elif op == 2:   # device write
                # direct server calls bypass the engine: drain queued
                # fire-and-forget adds first (the checkpoint.py:139 /
                # device-plane ownership convention)
                Zoo.Get().DrainServer()
                d = rng.standard_normal((len(ids), C)).astype(np.float32)
                srv.device_apply_rows(ids, d)
                np.add.at(oracle, ids, d)
            elif op == 3:   # device read
                Zoo.Get().DrainServer()
                rows = np.asarray(srv.device_fetch_rows(ids))
                np.testing.assert_allclose(rows, oracle[ids], rtol=1e-4,
                                           atol=1e-5)
            elif op == 4:   # fire-and-forget burst (engine window merge)
                for _ in range(int(rng.integers(2, 5))):
                    d = rng.standard_normal((len(ids), C)).astype(
                        np.float32)
                    table.AddFireForget(d, row_ids=ids)
                    np.add.at(oracle, ids, d)
            elif snapshot is not None and rng.random() < 0.5:
                # restore an OLDER snapshot (mutations happened since):
                # Load must discard everything after it
                Zoo.Get().DrainServer()
                blob, osnap = snapshot
                srv.Load(Stream(_io.BytesIO(blob)))
                oracle = osnap.copy()
            else:           # take a snapshot through the engine state
                Zoo.Get().DrainServer()
                buf = _io.BytesIO()
                srv.Store(Stream(buf))
                snapshot = (buf.getvalue(), oracle.copy())
        np.testing.assert_allclose(table.Get(), oracle, rtol=1e-4,
                                   atol=1e-5)

    @pytest.mark.parametrize("sparse", [False, True])
    def test_lr_device_windows_match_numpy(self, mv_env, sparse):
        """The LR device-plane window program against a from-scratch
        numpy model of the PS protocol: window-start weight cache,
        per-batch lr-scaled grads summed, one sgd application."""
        from multiverso_tpu.models.logreg.configure import Configure
        from multiverso_tpu.models.logreg.data import WindowReader
        import tempfile

        rng = np.random.default_rng(11)
        D, B, NB = 6, 8, 3
        n = B * NB * 4
        X = rng.normal(size=(n, D)).astype(np.float32)
        y = (X @ rng.normal(size=D) > 0).astype(int)
        with tempfile.TemporaryDirectory() as td:
            path = f"{td}/d.data"
            with open(path, "w") as f:
                for row, lab in zip(X, y):
                    if sparse:
                        f.write(f"{lab} " + " ".join(
                            f"{j}:{row[j]:.5f}" for j in range(D)) + "\n")
                    else:
                        f.write(f"{lab} " + " ".join(
                            f"{v:.5f}" for v in row) + "\n")
            cfg = Configure(input_size=D, output_size=1, sparse=sparse,
                            objective_type="sigmoid", updater_type="sgd",
                            learning_rate=0.3, train_epoch=1,
                            minibatch_size=B, sync_frequency=NB,
                            use_ps=True, device_plane=True, pipeline=False,
                            show_time_per_sample=10 ** 9, train_file=path,
                            test_file="", output_file="",
                            output_model_file="", cache_data=False)
            # numpy oracle of the same protocol over the same windows
            W = np.zeros((D, 1), np.float64)
            reader = WindowReader(path, cfg, NB)
            from multiverso_tpu.models.logreg.updater import (
                ClientSGDUpdater)
            upd = ClientSGDUpdater(cfg)
            while True:
                w = reader.next_window()
                if w is None:
                    break
                Wc = W.copy()            # window-start cache
                delta = np.zeros_like(W)
                for b in w.batches:
                    lr = upd.learning_rate()
                    upd.tick()
                    if sparse:
                        x = np.zeros((B, D), np.float64)
                        for i in range(B):
                            x[i, b.keys[i][b.mask[i] > 0]] = \
                                b.values[i][b.mask[i] > 0]
                    else:
                        x = b.dense.astype(np.float64)
                    act = 1 / (1 + np.exp(-(x @ Wc)))
                    onehot = (b.labels == 1).astype(np.float64)[:, None]
                    diff = (act - onehot) * b.weights[:, None]
                    count = max((b.weights > 0).sum(), 1)
                    grad = x.T @ diff / count
                    delta += lr * grad
                W = W - delta            # server sgd applies the sum
            # drive the real thing over the same file
            from multiverso_tpu.models.logreg.logreg import LogReg
            app = LogReg(cfg)
            try:
                app.Train()
                got = app.model.weights()
            finally:
                app.close()
            np.testing.assert_allclose(got, W, rtol=2e-3, atol=1e-5)
