"""Tier-1 (pure sharding/updater math) and tier-2 (full in-process PS path
over a real 8-device mesh) table tests.

Counterparts of reference Test/unittests/test_array.cpp, test_kv.cpp,
Test/test_matrix_table.cpp, and the binding accumulation invariants.
"""

import numpy as np
import pytest

from multiverso_tpu.parallel.mesh import partition_offsets, row_partition_server
from multiverso_tpu.tables import (ArrayTableOption, KVTableOption,
                                   MatrixTableOption, SparseMatrixTableOption)
from multiverso_tpu.updaters import AddOption, GetOption


# ---------------------------------------------------------------------------
# Tier 1: partition math as pure functions (reference test_array.cpp:47-66)
# ---------------------------------------------------------------------------

class TestPartitionMath:
    def test_array_partition_even(self):
        offs = partition_offsets(100, 4)
        assert offs == [(0, 25), (25, 25), (50, 25), (75, 25)]

    def test_array_partition_remainder_to_last(self):
        # last server takes the remainder (reference array_table.cpp:101-105)
        offs = partition_offsets(10, 4)
        assert offs == [(0, 2), (2, 2), (4, 2), (6, 4)]
        assert sum(c for _, c in offs) == 10

    def test_array_partition_tiny(self):
        offs = partition_offsets(3, 8)
        assert sum(c for _, c in offs) == 3

    def test_next_bucket_ladder(self):
        from multiverso_tpu.parallel.mesh import next_bucket
        # powers of two up to 256
        assert next_bucket(1) == 8
        assert next_bucket(9) == 16
        assert next_bucket(256) == 256
        # quarter-octave rungs above 256: waste <= 25%, 64-aligned
        assert next_bucket(257) == 320
        assert next_bucket(10_000) == 10_240
        assert next_bucket(16_384) == 16_384
        for n in (300, 1000, 5000, 10_000, 100_000, 123_457):
            b = next_bucket(n)
            assert b >= n and (b - n) <= n // 4 + 8
            if b > 256:
                assert b % 64 == 0

    def test_row_partition(self):
        # row -> server = row / (num_rows/num_servers), tail clamped
        # (reference matrix_table.cpp:24-46)
        assert row_partition_server(0, 100, 4) == 0
        assert row_partition_server(25, 100, 4) == 1
        assert row_partition_server(99, 100, 4) == 3
        assert row_partition_server(99, 101, 4) == 3  # tail clamp


# ---------------------------------------------------------------------------
# Tier 2: full PS path (reference test_array.cpp:27-45 etc.)
# ---------------------------------------------------------------------------

class TestArrayTable:
    def test_add_then_get(self, mv_env):
        table = mv_env.MV_CreateTable(ArrayTableOption(size=100))
        delta = np.arange(100, dtype=np.float32)
        table.Add(delta)
        table.Add(delta)
        np.testing.assert_allclose(table.Get(), 2 * delta)

    def test_async_handles(self, mv_env):
        table = mv_env.MV_CreateTable(ArrayTableOption(size=50))
        h1 = table.AddAsyncHandle(np.ones(50, np.float32))
        h2 = table.AddAsyncHandle(np.ones(50, np.float32))
        table.Wait(h1)
        table.Wait(h2)
        hg = table.GetAsyncHandle()
        np.testing.assert_allclose(table.Wait(hg), 2.0)

    def test_tiny_table_supported(self, mv_env):
        # improvement over reference (array_table.cpp:14 CHECK forbids this)
        table = mv_env.MV_CreateTable(ArrayTableOption(size=3))
        table.Add(np.array([1, 2, 3], np.float32))
        np.testing.assert_allclose(table.Get(), [1, 2, 3])

    def test_get_into_buffer(self, mv_env):
        table = mv_env.MV_CreateTable(ArrayTableOption(size=10))
        table.Add(np.full(10, 5.0, np.float32))
        buf = np.zeros(10, np.float32)
        out = table.Get(buffer=buf)
        assert out is buf
        np.testing.assert_allclose(buf, 5.0)

    def test_sgd_updater(self, mv_env):
        mv_env.MV_SetFlag("updater_type", "sgd")
        try:
            table = mv_env.MV_CreateTable(ArrayTableOption(size=10))
            table.Add(np.full(10, 0.5, np.float32))  # sgd: data -= delta
            np.testing.assert_allclose(table.Get(), -0.5)
        finally:
            mv_env.MV_SetFlag("updater_type", "default")

    def test_momentum_updater(self, mv_env):
        table = mv_env.MV_CreateTable(
            ArrayTableOption(size=4, updater_type="momentum"))
        opt = AddOption(momentum=0.5)
        delta = np.ones(4, np.float32)
        # smooth = .5*0 + .5*1 = .5 ; data = -0.5
        table.Add(delta, opt)
        np.testing.assert_allclose(table.Get(), -0.5)
        # smooth = .5*.5 + .5*1 = .75 ; data = -1.25
        table.Add(delta, opt)
        np.testing.assert_allclose(table.Get(), -1.25)

    def test_adagrad_updater_per_worker(self, mv_env):
        table = mv_env.MV_CreateTable(
            ArrayTableOption(size=4, updater_type="adagrad"))
        lr, rho = 1.0, 0.1
        opt0 = AddOption(worker_id=0, learning_rate=lr, rho=rho)
        delta = np.ones(4, np.float32)
        table.Add(delta, opt0)
        # hist=1, data -= rho*1/sqrt(1+eps)
        expected = -rho / np.sqrt(1 + 1e-6)
        np.testing.assert_allclose(table.Get(), expected, rtol=1e-5)

    def test_dcasgd_updater_delay_compensation(self):
        import multiverso_tpu as mv
        mv.MV_Init(["-num_workers=2"])
        try:
            table = mv.MV_CreateTable(
                ArrayTableOption(size=4, updater_type="dcasgd"))
            lr, lam = 0.1, 0.5
            delta = np.full(4, 0.2, np.float32)  # lr-scaled gradient
            opt0 = AddOption(worker_id=0, learning_rate=lr, lambda_=lam)
            # push 1 (worker 0): w=0, backup[0]=0 -> plain -delta
            table.Add(delta, opt0)
            w1 = -0.2
            np.testing.assert_allclose(table.Get(), w1, rtol=1e-5)
            # push 2 (worker 1, stale backup=0): compensation term kicks in
            opt1 = AddOption(worker_id=1, learning_rate=lr, lambda_=lam)
            table.Add(delta, opt1)
            w2 = w1 - (0.2 + (lam / lr) * 0.2 * 0.2 * (w1 - 0.0))
            np.testing.assert_allclose(table.Get(), w2, rtol=1e-5)
            # push 3 (worker 0 again): its backup is w1, not 0
            table.Add(delta, opt0)
            w3 = w2 - (0.2 + (lam / lr) * 0.2 * 0.2 * (w2 - w1))
            np.testing.assert_allclose(table.Get(), w3, rtol=1e-5)
        finally:
            mv.MV_ShutDown()

    def test_dcasgd_matrix_rows(self, mv_env):
        table = mv_env.MV_CreateTable(
            MatrixTableOption(num_rows=16, num_cols=4,
                              updater_type="dcasgd"))
        opt = AddOption(worker_id=0, learning_rate=0.1, lambda_=0.5)
        ids = np.array([2, 9, 14], np.int32)
        deltas = np.full((3, 4), 0.2, np.float32)
        table.AddRows(ids, deltas, opt)
        got = table.GetRows(ids)
        np.testing.assert_allclose(got, -0.2, rtol=1e-5)
        untouched = table.GetRows(np.array([0, 5], np.int32))
        np.testing.assert_allclose(untouched, 0.0)

    def test_store_load(self, mv_env, tmp_path):
        from multiverso_tpu.utils.io import StreamFactory
        from multiverso_tpu.zoo import Zoo
        table = mv_env.MV_CreateTable(ArrayTableOption(size=10))
        table.Add(np.arange(10, dtype=np.float32))
        server = Zoo.Get().server_tables[0]
        path = str(tmp_path / "ckpt.bin")
        with StreamFactory.GetStream(path, "w") as s:
            server.Store(s)
        table.Add(np.ones(10, np.float32))  # diverge
        with StreamFactory.GetStream(path, "r") as s:
            server.Load(s)
        np.testing.assert_allclose(table.Get(), np.arange(10))

    def test_partition_pure(self, mv_env):
        table = mv_env.MV_CreateTable(ArrayTableOption(size=100))
        offs = table.Partition(num_servers=4)
        assert offs == partition_offsets(100, 4)


class TestConcurrencyStress:
    """Tier-2 hammer (reference Test/test_array_table.cpp multi-worker
    accumulation invariant, scaled up): 8 worker threads mixing blocking,
    async-handle, and fire-and-forget verbs over three table kinds at
    once; exact accumulation invariants at the end."""

    def test_mixed_tables_hammer(self):
        import threading

        import multiverso_tpu as mv
        from multiverso_tpu.zoo import Zoo
        W, ITERS = 8, 20
        mv.MV_Init([f"-num_workers={W}"])
        try:
            arr = mv.MV_CreateTable(ArrayTableOption(size=64))
            mat = mv.MV_CreateTable(MatrixTableOption(num_rows=64,
                                                      num_cols=8))
            kv = mv.MV_CreateTable(KVTableOption())
            errors = []

            def work(wid):
                try:
                    with Zoo.Get().worker_context(wid):
                        rows = np.array([wid * 8 + i for i in range(8)],
                                        np.int32)
                        handles = []
                        for i in range(ITERS):
                            if i % 3 == 0:
                                arr.Add(np.ones(64, np.float32))
                            elif i % 3 == 1:
                                handles.append(arr.AddAsyncHandle(
                                    np.ones(64, np.float32)))
                            else:
                                arr.AddFireForget(np.ones(64, np.float32))
                            mat.AddRows(rows[i % 8: i % 8 + 1],
                                        np.ones((1, 8), np.float32))
                            kv.Add([wid, 1000 + wid], [1.0, 2.0])
                            if i % 5 == 0:
                                arr.Get()
                                mat.GetRows(rows)
                        for h in handles:
                            arr.Wait(h)
                except Exception as exc:  # pragma: no cover
                    errors.append(exc)

            ts = [threading.Thread(target=work, args=(w,)) for w in range(W)]
            [t.start() for t in ts]
            [t.join(timeout=120) for t in ts]
            assert not any(t.is_alive() for t in ts), "hammer deadlocked"
            assert not errors, errors
            Zoo.Get().DrainServer()   # fire-and-forget adds land
            np.testing.assert_allclose(arr.Get(), W * ITERS)
            got = mat.GetRows(np.arange(64, dtype=np.int32))
            # each worker hit its own 8 rows, row (wid*8 + j) exactly
            # ceil/floor of ITERS/8 times
            counts = got[:, 0].reshape(W, 8)
            for j in range(8):
                expect = len([i for i in range(ITERS) if i % 8 == j])
                np.testing.assert_allclose(counts[:, j], expect)
            np.testing.assert_allclose(
                kv.Get(list(range(W))), ITERS)
            np.testing.assert_allclose(
                kv.Get([1000 + w for w in range(W)]), 2 * ITERS)
        finally:
            mv.MV_ShutDown()


class TestUserExtensibleTable:
    """The reference proves its table interface is user-extensible by the LR
    app defining its own WorkerTable/ServerTable subclasses
    (Applications/LogisticRegression/src/util/sparse_table.h, SURVEY.md
    §2f). Same proof here: a custom max-merge table wired through
    CreateTable runs over the real engine with Waiter semantics intact."""

    def test_custom_table_through_engine(self, mv_env):
        from dataclasses import dataclass

        from multiverso_tpu.tables.base import (ServerTable, TableOption,
                                                WorkerTable)

        class MaxServerTable(ServerTable):
            def __init__(self, size):
                self.data = np.full(size, -np.inf, np.float32)

            def ProcessAdd(self, values, option):
                self.data = np.maximum(self.data, values)

            def ProcessGet(self, option):
                return self.data.copy()

        class MaxWorkerTable(WorkerTable):
            def Push(self, values):
                return self.Wait(self.AddAsync(
                    {"values": np.asarray(values, np.float32)}))

            def Pull(self):
                return self.Wait(self.GetAsync({}))

        @dataclass
        class MaxTableOption(TableOption):
            size: int = 0

            def make_server(self, zoo):
                return MaxServerTable(self.size)

            def make_worker(self, zoo):
                return MaxWorkerTable()

        table = mv_env.MV_CreateTable(MaxTableOption(size=4))
        table.Push([1.0, 5.0, -2.0, 0.0])
        table.Push([3.0, 4.0, -7.0, 1.0])
        np.testing.assert_allclose(table.Pull(), [3.0, 5.0, -2.0, 1.0])


class TestSingleServerFastPath:
    """num_servers == 1 drops the shard_map wrapper (and its psum) from
    the row programs — same lane semantics, verified by a random walk
    against the oracle on a 1-device world."""

    def test_oracle_walk_one_server(self):
        import jax

        import multiverso_tpu as mv
        mv.MV_Init([], devices=jax.devices()[:1])
        try:
            assert mv.MV_NumServers() == 1
            rng = np.random.default_rng(11)
            R, C = 73, 9
            table = mv.MV_CreateTable(MatrixTableOption(num_rows=R,
                                                        num_cols=C))
            oracle = np.zeros((R, C), np.float32)
            for _ in range(25):
                op = rng.integers(0, 3)
                if op == 0:
                    k = int(rng.integers(1, R + 1))
                    ids = rng.integers(0, R, k).astype(np.int32)
                    deltas = rng.standard_normal((k, C)).astype(np.float32)
                    table.AddRows(ids, deltas)
                    np.add.at(oracle, ids, deltas)
                elif op == 1:
                    k = int(rng.integers(1, R + 1))
                    ids = rng.integers(0, R, k).astype(np.int32)
                    np.testing.assert_allclose(table.GetRows(ids),
                                               oracle[ids],
                                               rtol=1e-5, atol=1e-5)
                else:
                    np.testing.assert_allclose(table.Get(), oracle,
                                               rtol=1e-5, atol=1e-5)
            # per-worker aux path too (adagrad off the fused kernel)
            t2 = mv.MV_CreateTable(MatrixTableOption(
                num_rows=8, num_cols=4, updater_type="adagrad"))
            t2.AddRows([1, 5], np.ones((2, 4), np.float32),
                       AddOption(worker_id=0, learning_rate=1.0, rho=0.1))
            np.testing.assert_allclose(
                t2.GetRows([1, 5]), -0.1 / np.sqrt(1 + 1e-6), rtol=1e-5)
        finally:
            mv.MV_ShutDown()


class TestDevicePlaneEager:
    """Public eager device-plane verbs (device_fetch_rows /
    device_apply_rows): host-plane validation semantics, data in HBM."""

    def test_fetch_apply_roundtrip(self, mv_env):
        import jax
        table = mv_env.MV_CreateTable(MatrixTableOption(num_rows=16,
                                                        num_cols=4))
        srv = table.server()
        ids = np.array([3, 7, 11], np.int32)
        rows = srv.device_fetch_rows(ids)
        assert isinstance(rows, jax.Array)
        np.testing.assert_allclose(np.asarray(rows), 0.0)
        srv.device_apply_rows(ids, np.ones((3, 4), np.float32))
        np.testing.assert_allclose(table.GetRows(ids), 1.0)

    def test_duplicates_pre_combined(self, mv_env):
        table = mv_env.MV_CreateTable(MatrixTableOption(num_rows=8,
                                                        num_cols=4))
        srv = table.server()
        ids = np.array([2, 5, 2], np.int32)   # duplicate id must stack
        deltas = np.ones((3, 4), np.float32)
        srv.device_apply_rows(ids, deltas)
        np.testing.assert_allclose(table.GetRows([2])[0], 2.0)
        np.testing.assert_allclose(table.GetRows([5])[0], 1.0)

    def test_out_of_range_raises(self, mv_env):
        from multiverso_tpu.utils.log import FatalError
        table = mv_env.MV_CreateTable(MatrixTableOption(num_rows=8,
                                                        num_cols=4))
        srv = table.server()
        with pytest.raises(FatalError):
            srv.device_fetch_rows([99])
        with pytest.raises(FatalError):
            srv.device_apply_rows([99], np.ones((1, 4), np.float32))


class TestDevicePlaneParts:
    """Batch-sharded 'parts' device-plane rounds — the multi-process SPMD
    path (each process's slice of a global batch merges on device,
    ops.dedup_rows combining duplicates by sum). Driven here on the
    single-process multi-device mesh; tests/test_multihost.py drives the
    real 2-process version."""

    def test_dedup_rows_matches_np_add_at(self, mv_env):
        import jax
        import jax.numpy as jnp
        from multiverso_tpu import ops
        rng = np.random.default_rng(3)
        ids = rng.integers(0, 10, size=32).astype(np.int32)
        ids[5:9] = -1   # pad lanes pass through
        deltas = rng.standard_normal((32, 4)).astype(np.float32)
        deltas[5:9] = 0.0
        oids, odeltas = jax.jit(ops.dedup_rows)(jnp.asarray(ids),
                                                jnp.asarray(deltas))
        oids, odeltas = np.asarray(oids), np.asarray(odeltas)
        expect = np.zeros((10, 4), np.float32)
        np.add.at(expect, ids[ids >= 0], deltas[ids >= 0])
        got = np.zeros((10, 4), np.float32)
        live = oids >= 0
        assert len(np.unique(oids[live])) == live.sum()  # no dup survives
        got[oids[live]] = odeltas[live]
        np.testing.assert_allclose(got, expect, rtol=1e-5)
        np.testing.assert_allclose(odeltas[~live], 0.0)

    def test_parts_round_equals_replicated_round(self, mv_env):
        from multiverso_tpu.updaters.base import AddOption
        table = mv_env.MV_CreateTable(MatrixTableOption(num_rows=24,
                                                        num_cols=4))
        srv = table.server()
        ids = np.array([1, 9, 1, 17], np.int32)   # duplicate id 1
        deltas = np.arange(16, dtype=np.float32).reshape(4, 4)
        gids, gdeltas = srv.device_place_batch(ids, deltas, bucket=8)
        srv.state = srv._update_rows_parts_j(srv.state, gids, gdeltas,
                                             AddOption().as_jnp())
        expect = np.zeros((24, 4), np.float32)
        np.add.at(expect, ids, deltas)
        np.testing.assert_allclose(table.Get(), expect, rtol=1e-6)
        # parts gather sees the same rows
        rows = srv._gather_rows_parts_j(srv.state["data"], srv.state["aux"],
                                        gids)
        np.testing.assert_allclose(np.asarray(rows)[:4], expect[ids],
                                   rtol=1e-6)

    def test_array_parts_delta_sums(self, mv_env):
        import jax
        from multiverso_tpu.tables import ArrayTableOption
        from multiverso_tpu.updaters.base import AddOption
        table = mv_env.MV_CreateTable(ArrayTableOption(size=16))
        asrv = table.server()
        parts = asrv.device_place_parts_delta(np.full(16, 2.0, np.float32))
        state = jax.jit(asrv.device_update_parts, donate_argnums=(0,))(
            asrv.device_state(), parts, AddOption().as_jnp())
        asrv.device_set_state(state)
        np.testing.assert_allclose(table.Get(), 2.0)

    def test_kv_parts_scatter_add(self, mv_env):
        import jax
        from multiverso_tpu.tables import KVTableOption
        table = mv_env.MV_CreateTable(KVTableOption())
        ksrv = table.server()
        slots = ksrv.device_slots(np.array([7, 9, 7], np.int64),
                                  create=True)
        deltas = np.zeros(len(slots), np.float32)
        deltas[:3] = 1.0
        gslots, gdeltas = ksrv.device_place_slots(slots, deltas)
        vals = jax.jit(ksrv.device_scatter_add_slots, donate_argnums=(0,))(
            ksrv.device_values(), gslots, gdeltas)
        ksrv.device_set_values(vals)
        got = table.Get(np.array([7, 9], np.int64))
        np.testing.assert_allclose(got, [2.0, 1.0])  # dup key accumulated


class TestMatrixTable:
    def test_whole_add_get(self, mv_env):
        table = mv_env.MV_CreateTable(MatrixTableOption(num_rows=20, num_cols=5))
        delta = np.random.default_rng(0).normal(size=(20, 5)).astype(np.float32)
        table.Add(delta)
        np.testing.assert_allclose(table.Get(), delta, rtol=1e-6)

    def test_row_add_get(self, mv_env):
        table = mv_env.MV_CreateTable(MatrixTableOption(num_rows=100, num_cols=8))
        ids = [3, 17, 99]
        deltas = np.ones((3, 8), np.float32) * np.array([[1], [2], [3]],
                                                        np.float32)
        table.AddRows(ids, deltas)
        rows = table.GetRows([99, 3, 17])
        np.testing.assert_allclose(rows[:, 0], [3, 1, 2])
        # untouched rows stay zero
        np.testing.assert_allclose(table.GetRows([50]), 0)

    def test_duplicate_row_ids_accumulate(self, mv_env):
        table = mv_env.MV_CreateTable(MatrixTableOption(num_rows=10, num_cols=4))
        table.AddRows([2, 2, 2], np.ones((3, 4), np.float32))
        np.testing.assert_allclose(table.GetRows([2]), 3.0)

    def test_initializer(self, mv_env):
        rng = np.random.default_rng(42)
        init = rng.normal(size=(10, 4)).astype(np.float32)
        table = mv_env.MV_CreateTable(
            MatrixTableOption(num_rows=10, num_cols=4,
                              initializer=lambda shape: init))
        np.testing.assert_allclose(table.Get(), init, rtol=1e-6)

    def test_varied_batch_sizes_bucket(self, mv_env):
        table = mv_env.MV_CreateTable(MatrixTableOption(num_rows=64, num_cols=4))
        for k in (1, 2, 3, 9, 17, 33):
            table.AddRows(np.arange(k), np.ones((k, 4), np.float32))
        rows = table.GetRows(np.arange(33))
        assert rows[0, 0] == 6  # row 0 hit by all six adds

    def test_store_load(self, mv_env, tmp_path):
        from multiverso_tpu.utils.io import StreamFactory
        from multiverso_tpu.zoo import Zoo
        table = mv_env.MV_CreateTable(MatrixTableOption(num_rows=6, num_cols=3))
        table.Add(np.full((6, 3), 2.0, np.float32))
        server = Zoo.Get().server_tables[0]
        path = str(tmp_path / "m.bin")
        with StreamFactory.GetStream(path, "w") as s:
            server.Store(s)
        table.Add(np.ones((6, 3), np.float32))
        with StreamFactory.GetStream(path, "r") as s:
            server.Load(s)
        np.testing.assert_allclose(table.Get(), 2.0)

    def test_partition_by_server(self, mv_env):
        table = mv_env.MV_CreateTable(MatrixTableOption(num_rows=100, num_cols=2))
        buckets = table.Partition([0, 25, 50, 99], num_servers=4)
        assert buckets == {0: [0], 1: [25], 2: [50], 3: [99]}


class TestKVTable:
    def test_add_get(self, mv_env):
        table = mv_env.MV_CreateTable(KVTableOption())
        table.Add([1, 2, 10**12], [1.0, 2.0, 3.0])
        np.testing.assert_allclose(table.Get([10**12, 2, 1]), [3.0, 2.0, 1.0])

    def test_missing_key_zero(self, mv_env):
        table = mv_env.MV_CreateTable(KVTableOption())
        np.testing.assert_allclose(table.Get([123456]), [0.0])

    def test_accumulate_and_duplicates(self, mv_env):
        table = mv_env.MV_CreateTable(KVTableOption())
        table.Add([7, 7, 7], [1.0, 2.0, 3.0])
        table.Add([7], [4.0])
        np.testing.assert_allclose(table.Get([7]), [10.0])

    def test_growth(self, mv_env):
        table = mv_env.MV_CreateTable(KVTableOption(init_capacity=8))
        keys = np.arange(100, dtype=np.int64)
        table.Add(keys, np.ones(100, np.float32))
        np.testing.assert_allclose(table.Get(keys), 1.0)

    def test_local_cache(self, mv_env):
        table = mv_env.MV_CreateTable(KVTableOption())
        table.Add([5], [2.0])
        table.Get([5])
        assert table.raw()[5] == 2.0

    def test_int64_values(self, mv_env):
        # WE word-count table is KVTable<int, int64> (reference
        # communicator.cpp:17-33)
        table = mv_env.MV_CreateTable(KVTableOption(dtype=np.int64))
        table.Add([1], [2**40])
        assert table.Get([1])[0] == 2**40

    def test_store_load(self, mv_env, tmp_path):
        from multiverso_tpu.utils.io import StreamFactory
        from multiverso_tpu.zoo import Zoo
        table = mv_env.MV_CreateTable(KVTableOption())
        table.Add([3, 9], [1.5, 2.5])
        server = Zoo.Get().server_tables[0]
        path = str(tmp_path / "kv.bin")
        with StreamFactory.GetStream(path, "w") as s:
            server.Store(s)
        table.Add([3], [10.0])
        with StreamFactory.GetStream(path, "r") as s:
            server.Load(s)
        np.testing.assert_allclose(table.Get([3, 9]), [1.5, 2.5])


class TestKVDevicePlane:
    """KV device plane (kv_table.py device_*): resolve keys once on host,
    trace gather/scatter-add over the sharded values array inside a
    scanned step — the matrix device plane's KV counterpart."""

    def test_traced_rounds_match_host_plane(self, mv_env):
        import jax
        import jax.numpy as jnp
        from jax import lax
        table = mv_env.MV_CreateTable(KVTableOption())
        server = table.server()
        keys = np.array([5, 9, 9, 17, 10**12], np.int64)
        slots = server.device_slots(keys, create=True)  # resolve + pad
        deltas = np.zeros(len(slots), np.float32)
        deltas[: len(keys)] = [1.0, 2.0, 3.0, 4.0, 5.0]  # pad lanes: zero

        @jax.jit
        def rounds(values, slots, deltas):
            def body(values, _):
                values = server.device_scatter_add_slots(values, slots,
                                                         deltas)
                got = server.device_gather_slots(values, slots)
                return values, got[0]
            return lax.scan(body, values, jnp.arange(3))

        values, ys = rounds(server.device_values(), jnp.asarray(slots),
                            jnp.asarray(deltas))
        server.device_set_values(values)
        # duplicates accumulated (key 9: 2+3 per round), 3 rounds total,
        # and the HOST plane sees the device writes
        np.testing.assert_allclose(table.Get(np.array([5, 9, 17, 10**12])),
                                   [3.0, 15.0, 12.0, 15.0])
        np.testing.assert_allclose(np.asarray(ys), [1.0, 2.0, 3.0])

    def test_absent_keys_and_growth_order(self, mv_env):
        import jax.numpy as jnp
        table = mv_env.MV_CreateTable(KVTableOption(init_capacity=8))
        server = table.server()
        # create=False: absent keys pad to the trash slot (masked reads)
        slots = server.device_slots(np.array([42], np.int64), create=False)
        assert slots[0] == server.capacity - 1
        # growth happens AT RESOLVE time: resolve first, then take values
        many = np.arange(100, dtype=np.int64)
        slots = server.device_slots(many, create=True)
        values = server.device_values()
        assert values.shape[0] == server.capacity >= 100
        deltas = np.zeros(len(slots), np.float32)
        deltas[:100] = 1.0
        values = server.device_scatter_add_slots(
            values, jnp.asarray(slots), jnp.asarray(deltas))
        server.device_set_values(values)
        np.testing.assert_allclose(table.Get(many), 1.0)

    def test_host_backed_dtype_rejected(self, mv_env):
        from multiverso_tpu.utils.log import FatalError
        table = mv_env.MV_CreateTable(KVTableOption(dtype=np.int64))
        with pytest.raises(FatalError):
            table.server().device_slots(np.array([1], np.int64))

    def test_drifted_writeback_dtype_rejected(self, mv_env):
        import jax.numpy as jnp
        from multiverso_tpu.utils.log import FatalError
        table = mv_env.MV_CreateTable(KVTableOption())
        server = table.server()
        server.device_slots(np.array([1], np.int64), create=True)
        bad = server.device_values().astype(jnp.bfloat16)
        with pytest.raises(FatalError):
            server.device_set_values(bad)  # would corrupt Store/Load


class TestSparseMatrixTable:
    def _make(self, mv, workers=2):
        return mv.MV_CreateTable(
            SparseMatrixTableOption(num_rows=10, num_cols=3))

    def test_dirty_row_protocol(self):
        import multiverso_tpu as mv
        mv.MV_Init(["-num_workers=2"])
        try:
            table = self._make(mv)
            # worker 0 adds rows 2,4 -> stale for worker 1, fresh for worker 0
            table.AddRows([2, 4], np.ones((2, 3), np.float32),
                          AddOption(worker_id=0))
            ids, rows = table.Get(GetOption(worker_id=1))
            assert sorted(ids.tolist()) == [2, 4]
            np.testing.assert_allclose(rows, 1.0)
            # second get: nothing stale -> row 0 fallback
            ids2, _ = table.Get(GetOption(worker_id=1))
            assert ids2.tolist() == [0]
            # adder itself sees nothing stale
            ids3, _ = table.Get(GetOption(worker_id=0))
            assert ids3.tolist() == [0]
        finally:
            mv.MV_ShutDown()

    def test_worker_minus_one_gets_all(self):
        import multiverso_tpu as mv
        mv.MV_Init(["-num_workers=2"])
        try:
            table = self._make(mv)
            ids, rows = table.Get(GetOption(worker_id=-1))
            assert len(ids) == 10
            assert rows.shape == (10, 3)
        finally:
            mv.MV_ShutDown()

    def test_ownerless_add_marks_everyone_stale(self):
        """An Add with worker_id=-1 (a system-level push with no owning
        worker — reference UpdateAddState tolerates out-of-range ids) has
        no keeper: every worker sees the rows stale."""
        import multiverso_tpu as mv
        mv.MV_Init(["-num_workers=2"])
        try:
            table = self._make(mv)
            table.AddRows([3, 6], np.ones((2, 3), np.float32),
                          AddOption(worker_id=-1))
            for w in (0, 1):
                ids, rows = table.Get(GetOption(worker_id=w))
                assert sorted(ids.tolist()) == [3, 6], (w, ids)
                np.testing.assert_allclose(rows, 1.0)
        finally:
            mv.MV_ShutDown()

    def test_get_rows_subset(self):
        import multiverso_tpu as mv
        mv.MV_Init(["-num_workers=2"])
        try:
            table = self._make(mv)
            table.AddRows([1, 5, 7], np.ones((3, 3), np.float32),
                          AddOption(worker_id=0))
            # worker 1 asks about rows [5, 6]: only 5 is stale
            ids, rows = table.GetRows([5, 6], GetOption(worker_id=1))
            assert ids.tolist() == [5]
        finally:
            mv.MV_ShutDown()


class TestErrorPropagation:
    """Regression tests for review findings: server-side failures must reach
    the caller's Wait() and must not corrupt neighbouring requests."""

    def test_add_size_mismatch_raises_at_caller(self, mv_env):
        from multiverso_tpu.utils.log import FatalError
        table = mv_env.MV_CreateTable(ArrayTableOption(size=10))
        with pytest.raises(FatalError):
            table.Add(np.ones(7, np.float32))
        table.Add(np.ones(10, np.float32))  # table still healthy
        np.testing.assert_allclose(table.Get(), 1.0)

    def test_negative_row_id_rejected(self, mv_env):
        from multiverso_tpu.utils.log import FatalError
        table = mv_env.MV_CreateTable(MatrixTableOption(num_rows=15, num_cols=2))
        with pytest.raises(FatalError):
            table.AddRows([-3], np.ones((1, 2), np.float32))
        with pytest.raises(FatalError):
            table.GetRows([-1])
        np.testing.assert_allclose(table.Get(), 0.0)  # nothing leaked in

    def test_get_duplicates_exceeding_padded_rows(self, mv_env):
        # Get path allows duplicates; batches longer than the table must work
        table = mv_env.MV_CreateTable(MatrixTableOption(num_rows=5, num_cols=2))
        table.AddRows([0, 1, 2], np.ones((3, 2), np.float32))
        ids = [0, 1, 2, 0, 1, 2, 0, 1, 2, 0]
        rows = table.GetRows(ids)
        assert rows.shape == (10, 2)
        np.testing.assert_allclose(rows, 1.0)

    def test_failed_add_does_not_desync_sparse_bits(self):
        import multiverso_tpu as mv
        from multiverso_tpu.utils.log import FatalError
        mv.MV_Init(["-num_workers=2"])
        try:
            table = mv.MV_CreateTable(
                SparseMatrixTableOption(num_rows=10, num_cols=2))
            with pytest.raises(FatalError):
                table.AddRows([99], np.ones((1, 2), np.float32),
                              AddOption(worker_id=0))
            ids, _ = table.Get(GetOption(worker_id=1))
            assert ids.tolist() == [0]  # nothing became stale
        finally:
            mv.MV_ShutDown()

    def test_drained_message_error_reaches_its_own_caller(self):
        """SyncServer drain path: a failing cached Get must fail for ITS
        worker, not poison the draining worker's request."""
        import threading
        import multiverso_tpu as mv
        from multiverso_tpu.utils.log import FatalError
        mv.MV_Init(["-num_workers=2", "-sync=true"])
        try:
            table = mv.MV_CreateTable(MatrixTableOption(num_rows=5, num_cols=2))
            outcome = {}

            def worker_b():
                from multiverso_tpu.zoo import Zoo
                with Zoo.Get().worker_context(1):
                    table.AddRows([0], np.ones((1, 2), np.float32),
                                  AddOption(worker_id=1))
                    try:
                        table.GetRows([99], GetOption(worker_id=1))
                        outcome["b"] = "no-error"
                    except FatalError:
                        outcome["b"] = "raised"

            tb = threading.Thread(target=worker_b)
            tb.start()
            import time
            time.sleep(0.3)  # let B's Get reach the server first
            from multiverso_tpu.zoo import Zoo
            with Zoo.Get().worker_context(0):
                table.AddRows([0], np.ones((1, 2), np.float32),
                              AddOption(worker_id=0))  # must NOT raise
                outcome["a"] = "ok"
            tb.join(timeout=30)
            assert not tb.is_alive(), "worker B hung"
            assert outcome == {"a": "ok", "b": "raised"}
        finally:
            mv.MV_ShutDown()


class TestArrayDevicePlane:
    """Array device plane (array_table.py device_*): whole-table updater
    rounds scanned into the caller's XLA program."""

    def test_traced_sgd_rounds_match_host_plane(self, mv_env):
        import jax
        import jax.numpy as jnp
        from jax import lax
        table = mv_env.MV_CreateTable(ArrayTableOption(size=10,
                                                       updater_type="sgd"))
        server = table.server()
        delta = np.zeros(server.padded, np.float32)
        delta[:10] = 0.5
        opt = AddOption().as_jnp()

        @jax.jit
        def rounds(state, delta):
            def body(state, _):
                state = server.device_update(state, delta, opt)
                return state, server.device_access(state)[0]
            return lax.scan(body, state, jnp.arange(4))

        state, ys = rounds(server.device_state(), jnp.asarray(delta))
        server.device_set_state(state)
        # sgd: data -= delta, 4 rounds; host plane sees the device writes
        np.testing.assert_allclose(table.Get(), -2.0)
        np.testing.assert_allclose(np.asarray(ys), [-0.5, -1.0, -1.5, -2.0])

    def test_adagrad_aux_rides_the_carry(self):
        import jax
        import jax.numpy as jnp
        import multiverso_tpu as mv
        mv.MV_Init(["-num_workers=2"])
        try:
            table = mv.MV_CreateTable(ArrayTableOption(
                size=8, updater_type="adagrad"))
            server = table.server()
            delta = np.full(server.padded, 0.2, np.float32)
            opt = AddOption(worker_id=1, learning_rate=0.1,
                            rho=0.3).as_jnp()
            state = server.device_state()
            state = jax.jit(server.device_update)(state, jnp.asarray(delta),
                                                  opt)
            server.device_set_state(state)
            got = table.Get()
            assert np.all(np.isfinite(got)) and np.all(got != 0)
            # per-worker hist updated for worker 1 only
            hist = server.aux_to_logical("hist", state["aux"]["hist"])
            assert hist.shape[0] == 2
            assert np.all(hist[1] > 0) and np.all(hist[0] == 0)
        finally:
            mv.MV_ShutDown()

    def test_bad_writeback_rejected(self, mv_env):
        import jax.numpy as jnp
        from multiverso_tpu.utils.log import FatalError
        table = mv_env.MV_CreateTable(ArrayTableOption(size=8))
        server = table.server()
        state = dict(server.device_state())
        state["data"] = state["data"].astype(jnp.bfloat16)
        with pytest.raises(FatalError):
            server.device_set_state(state)


class TestWireCompression:
    """compress="sparse"/"1bit" on the matrix wire (TableOption.compress):
    payloads cross the host<->device boundary compressed and reconstruct
    inside the jit'd consumer."""

    def test_sparse_filter_is_exact(self, mv_env):
        rng = np.random.default_rng(9)
        plain = mv_env.MV_CreateTable(
            MatrixTableOption(num_rows=200, num_cols=8))
        comp = mv_env.MV_CreateTable(
            MatrixTableOption(num_rows=200, num_cols=8, compress="sparse"))
        for _ in range(5):
            ids = rng.choice(200, 30, replace=False).astype(np.int32)
            deltas = rng.standard_normal((30, 8)).astype(np.float32)
            deltas[rng.random((30, 8)) < 0.8] = 0.0   # sparse payload
            plain.AddRows(ids, deltas)
            comp.AddRows(ids, deltas)
            # dense payload -> the >50%-zeros rule falls back, still exact
            dense_ids = rng.choice(200, 10, replace=False).astype(np.int32)
            dense = rng.standard_normal((10, 8)).astype(np.float32)
            plain.AddRows(dense_ids, dense)
            comp.AddRows(dense_ids, dense)
        np.testing.assert_allclose(comp.Get(), plain.Get(), rtol=1e-6)
        stats = comp.server().wire_stats
        assert stats["dense_bytes"] > 0
        assert stats["payload_bytes"] < stats["dense_bytes"]

    def test_sparse_compress_with_duplicates_and_trash(self, mv_env):
        table = mv_env.MV_CreateTable(
            MatrixTableOption(num_rows=50, num_cols=4, compress="sparse"))
        ids = np.array([3, 7, 3], np.int32)       # duplicate pre-combines
        deltas = np.zeros((3, 4), np.float32)
        deltas[0, 1] = 1.0
        deltas[2, 1] = 2.0
        deltas[1, 3] = 5.0
        table.AddRows(ids, deltas)
        got = table.GetRows(np.array([3, 7], np.int32))
        np.testing.assert_allclose(got[0], [0, 3.0, 0, 0])
        np.testing.assert_allclose(got[1], [0, 0, 0, 5.0])

    def test_1bit_error_feedback_converges(self, mv_env):
        """Repeated pushes of the same delta: per-push reconstruction is
        lossy, but the error feedback makes the CUMULATIVE applied delta
        track the cumulative true delta."""
        table = mv_env.MV_CreateTable(
            MatrixTableOption(num_rows=32, num_cols=64, compress="1bit"))
        rng = np.random.default_rng(3)
        ids = np.arange(32, dtype=np.int32)
        true_delta = rng.standard_normal((32, 64)).astype(np.float32)
        # the residual is BOUNDED (error feedback) so the relative error
        # of the cumulative sum decays as O(1/n); the bound scales with
        # the within-row spread (measured: rel ~0.34 at n=40, ~0.10 at
        # n=160 for 64-col gaussian rows)
        rels = []
        n = 0
        for stage in (40, 120):
            for _ in range(stage):
                table.AddRows(ids, true_delta)
            n += stage
            got = table.Get()
            rels.append(np.abs(got - n * true_delta).max()
                        / (n * np.abs(true_delta).max()))
        assert rels[-1] < 0.15, rels
        assert rels[-1] < rels[0] * 0.5, rels   # genuine 1/n decay
        stats = table.server().wire_stats
        assert stats["payload_bytes"] * 8 < stats["dense_bytes"]

    def test_unsupported_tables_reject_compress(self, mv_env):
        from multiverso_tpu.utils.log import FatalError
        with pytest.raises(FatalError):
            mv_env.MV_CreateTable(ArrayTableOption(size=8,
                                                   compress="sparse"))
        with pytest.raises(FatalError):
            mv_env.MV_CreateTable(KVTableOption(compress="1bit"))
        # SparseMatrixTable FORWARDS compress (it is a matrix table):
        # the compressed add applies and the data is exact
        sp = mv_env.MV_CreateTable(SparseMatrixTableOption(
            num_rows=40, num_cols=8, compress="sparse"))
        d = np.zeros((2, 8), np.float32)
        d[0, 0] = 1.0
        sp.AddRows(np.array([1, 5], np.int32), d,
                   AddOption(worker_id=0))
        raw = sp.server().raw()
        np.testing.assert_allclose(raw[1, 0], 1.0)
        np.testing.assert_allclose(raw[5], 0.0)

    def test_compressed_adds_coalesce_safely(self, mv_env):
        """Compressed payloads decline the engine's merged window (values
        are absent) and still accumulate exactly."""
        table = mv_env.MV_CreateTable(
            MatrixTableOption(num_rows=64, num_cols=4, compress="sparse"))
        oracle = np.zeros((64, 4), np.float32)
        rng = np.random.default_rng(4)
        for _ in range(6):
            ids = rng.choice(64, 16, replace=False).astype(np.int32)
            deltas = rng.standard_normal((16, 4)).astype(np.float32)
            deltas[rng.random((16, 4)) < 0.9] = 0.0
            table.AddFireForget(deltas, row_ids=ids)
            np.add.at(oracle, ids, deltas)
        got = table.GetRows(np.arange(64, dtype=np.int32))
        np.testing.assert_allclose(got, oracle, rtol=1e-5, atol=1e-6)


class TestWindowBarrier:
    def test_store_load_barriers_add_coalescing(self, mv_env):
        """A Request_StoreLoad drained into an engine window must SPLIT the
        window's add-coalescing: an Add enqueued after a Load would
        otherwise be merged to the first Add's position, applied before
        the restore, and silently wiped (the bridge's store/load rides
        the mailbox precisely to be ordered against Adds)."""
        import io as _io
        import time
        from multiverso_tpu.message import Message, MsgType
        from multiverso_tpu.utils.io import Stream
        from multiverso_tpu.utils.waiter import Waiter
        from multiverso_tpu.zoo import Zoo

        table = mv_env.MV_CreateTable(MatrixTableOption(num_rows=16,
                                                        num_cols=4))
        srv = table.server()
        ids = np.arange(16, dtype=np.int32)
        base = np.full((16, 4), 2.0, np.float32)
        table.AddRows(ids, base)           # tracked: lands before snapshot

        def engine_submit(fn, wait=True):
            w = Waiter(1)
            msg = Message(msg_type=MsgType.Request_StoreLoad,
                          payload={"fn": fn}, waiter=w)
            Zoo.Get().SendToServer(msg)
            if wait:
                w.Wait()
                if isinstance(msg.result, Exception):
                    raise msg.result
            return w, msg

        buf = _io.BytesIO()
        engine_submit(lambda: srv.Store(Stream(buf)))
        snapshot = buf.getvalue()

        # jam the engine so everything below queues into ONE window
        engine_submit(lambda: time.sleep(0.4), wait=False)
        d1 = np.full((16, 4), 5.0, np.float32)    # applied, then restored over
        d2 = np.full((16, 4), 11.0, np.float32)   # applied AFTER the restore
        table.AddFireForget(d1, row_ids=ids)
        w_load, m_load = engine_submit(
            lambda: srv.Load(Stream(_io.BytesIO(snapshot))), wait=False)
        table.AddFireForget(d2, row_ids=ids)
        got = table.GetRows(ids)                  # drains behind the window
        w_load.Wait()
        assert not isinstance(m_load.result, Exception), m_load.result
        # the test is only meaningful if the Load actually landed INSIDE
        # a drained window (otherwise everything processed singly and the
        # assertion would hold even on pre-barrier coalescing code)
        assert Zoo.Get().server_engine.window_barrier_splits >= 1
        np.testing.assert_allclose(got, base + d2, rtol=1e-6)
        np.testing.assert_allclose(table.GetRows(ids), base + d2, rtol=1e-6)


class TestHostVerbsAroundOtherPlanes:
    """A MatrixTable's host verbs beside the table's other planes: the
    updater's sign, a device-plane write between them, Store / Load."""

    def test_sgd_sign_through_host_adds(self, mv_env):
        table = mv_env.MV_CreateTable(MatrixTableOption(
            num_rows=32, num_cols=4, updater_type="sgd"))
        ids = np.arange(32, dtype=np.int32)
        table.AddRows(ids, np.full((32, 4), 3.0, np.float32))
        np.testing.assert_array_equal(table.GetRows(ids), -3.0)
        table.Add(np.full((32, 4), 2.0, np.float32))
        np.testing.assert_array_equal(table.Get(), -5.0)

    def test_device_write_between_host_verbs_reads_back(self, mv_env):
        table = mv_env.MV_CreateTable(MatrixTableOption(num_rows=64,
                                                        num_cols=4))
        srv = table.server()
        ids = np.arange(64, dtype=np.int32)
        table.AddRows(ids, np.full((64, 4), 2.0, np.float32))
        srv.device_apply_rows(np.array([0, 1], np.int32),
                              np.ones((2, 4), np.float32))
        expect = np.full((64, 4), 2.0, np.float32)
        expect[:2] += 1.0
        np.testing.assert_array_equal(table.GetRows(ids), expect)
        table.AddRows(ids[:2], np.ones((2, 4), np.float32))
        expect[:2] += 1.0
        np.testing.assert_array_equal(
            np.asarray(srv.device_fetch_rows(ids)), expect)
        np.testing.assert_array_equal(srv.raw(), expect)

    def test_store_load_roundtrip_after_host_adds(self, mv_env):
        import io as _io
        from multiverso_tpu.utils.io import Stream
        table = mv_env.MV_CreateTable(MatrixTableOption(num_rows=16,
                                                        num_cols=4))
        srv = table.server()
        ids = np.arange(16, dtype=np.int32)
        table.AddRows(ids, np.full((16, 4), 5.0, np.float32))
        buf = _io.BytesIO()
        srv.Store(Stream(buf))
        table.AddRows(ids, np.full((16, 4), 9.0, np.float32))
        srv.Load(Stream(_io.BytesIO(buf.getvalue())))
        np.testing.assert_array_equal(table.GetRows(ids), 5.0)


class TestKVHostMirror:
    """CPU-backend host mirror for the f32 KV values: host verbs apply
    with numpy; device-plane reads sync, device-plane writes drop it."""

    def test_mirror_interleaves_with_device_plane(self, mv_env):
        import jax.numpy as jnp
        kv = mv_env.MV_CreateTable(KVTableOption())
        srv = kv.server()
        keys = np.arange(100, dtype=np.int64) * 13
        kv.Add(keys, np.full(100, 2.0, np.float32))     # host (mirror)
        assert srv._values_np is not None and srv._np_dirty
        # device-plane read syncs pending host writes
        slots = srv.device_slots(keys[:10])
        vals = srv.device_values()
        assert not srv._np_dirty
        got = np.asarray(srv.device_gather_slots(vals, jnp.asarray(slots)))
        np.testing.assert_allclose(got[:10], 2.0)
        # device-plane write drops the mirror; later host Get rebuilds
        pad_d = np.zeros(len(slots), np.float32)
        pad_d[:10] = 1.0
        srv.device_set_values(srv.device_scatter_add_slots(
            vals, jnp.asarray(slots), jnp.asarray(pad_d)))
        assert srv._values_np is None
        np.testing.assert_allclose(kv.Get(keys[:10]), 3.0)
        np.testing.assert_allclose(kv.Get(keys[10:]), 2.0)

    def test_checkpoint_with_dirty_mirror(self, mv_env):
        import io as _io
        from multiverso_tpu.utils.io import Stream
        kv = mv_env.MV_CreateTable(KVTableOption())
        srv = kv.server()
        keys = np.array([5, -17, 2**40], np.int64)
        kv.Add(keys, np.array([1.0, 2.0, 3.0], np.float32))
        assert srv._np_dirty or srv._values_np is None  # mirror or no-lib
        buf = _io.BytesIO()
        srv.Store(Stream(buf))
        kv.Add(keys, np.full(3, 50.0, np.float32))
        srv.Load(Stream(_io.BytesIO(buf.getvalue())))
        np.testing.assert_allclose(kv.Get(keys), [1.0, 2.0, 3.0])

    def test_growth_keeps_mirror_authoritative(self, mv_env):
        kv = mv_env.MV_CreateTable(KVTableOption(init_capacity=64))
        srv = kv.server()
        rng = np.random.default_rng(3)
        oracle = {}
        for _ in range(6):
            keys = rng.integers(0, 10**9, 500)
            vals = rng.standard_normal(500).astype(np.float32)
            kv.Add(keys, vals)
            for k, v in zip(keys.tolist(), vals.tolist()):
                oracle[k] = oracle.get(k, 0.0) + v
        probe = np.fromiter(oracle.keys(), np.int64, len(oracle))
        expect = np.array([oracle[int(k)] for k in probe], np.float32)
        np.testing.assert_allclose(kv.Get(probe), expect, rtol=1e-4,
                                   atol=1e-5)
