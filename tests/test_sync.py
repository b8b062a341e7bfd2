"""Consistency-mode tests: async vs BSP sync servers, vector clocks,
model-average allreduce.

Counterparts of reference Test/unittests/test_sync.cpp,
Test/test_array_table.cpp (sync multi-worker accumulation invariant) and
Test/test_allreduce.cpp.

The BSP guarantee is held here on an ArrayTable against a closed form;
``tests/test_bsp_rounds.py`` holds it on a MatrixTable, every Get of
every round against the plain reference ``tables/bsp_reference.py``.
"""

import threading

import numpy as np
import pytest

from multiverso_tpu.sync.server import VectorClock
from multiverso_tpu.tables import ArrayTableOption
from multiverso_tpu.updaters import AddOption, GetOption


class TestVectorClock:
    """Tier-1: the clock math alone (reference server.cpp:81-137)."""

    def test_round_completion(self):
        vc = VectorClock(3)
        assert not vc.Update(0)
        assert not vc.Update(1)
        assert vc.Update(2)  # all at 1 -> round completes
        assert vc.global_clock() == 1

    def test_uneven_progress(self):
        vc = VectorClock(2)
        assert not vc.Update(0)
        assert not vc.Update(0)  # worker 0 ran ahead to 2
        assert not vc.Update(1)  # min=1, global->1, but max=2 -> not complete
        assert vc.global_clock() == 1
        assert vc.Update(1)      # both at 2 -> complete
        assert vc.global_clock() == 2

    def test_finish_train(self):
        vc = VectorClock(2)
        vc.Update(0)
        assert vc.FinishTrain(0) is False  # worker 1 still at 0
        assert vc.FinishTrain(1) is True   # everyone infinite -> drains


class TestSyncServerInvariant:
    """The BSP guarantee (reference server.cpp:60-67): with -sync=true,
    every worker's i-th Get returns identical parameters, equal to the state
    after all workers' i-th Adds. Mirrors Test/test_array_table.cpp:13-47."""

    NUM_WORKERS = 4
    ITERS = 5
    SIZE = 32

    def _worker(self, mv, table, wid, results, errors):
        try:
            from multiverso_tpu.zoo import Zoo
            with Zoo.Get().worker_context(wid):
                delta = np.full(self.SIZE, float(wid + 1), np.float32)
                for it in range(self.ITERS):
                    table.Add(delta, AddOption(worker_id=wid))
                    got = table.Get(option=GetOption(worker_id=wid))
                    results[wid].append(got.copy())
        except Exception as e:  # pragma: no cover
            errors.append((wid, e))

    def test_bsp_accumulation(self):
        import multiverso_tpu as mv
        mv.MV_Init([f"-num_workers={self.NUM_WORKERS}", "-sync=true"])
        try:
            table = mv.MV_CreateTable(ArrayTableOption(size=self.SIZE))
            results = [[] for _ in range(self.NUM_WORKERS)]
            errors = []
            threads = [threading.Thread(target=self._worker,
                                        args=(mv, table, w, results, errors))
                       for w in range(self.NUM_WORKERS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not errors, errors
            per_round = sum(w + 1 for w in range(self.NUM_WORKERS))
            for it in range(self.ITERS):
                expected = per_round * (it + 1)
                for wid in range(self.NUM_WORKERS):
                    np.testing.assert_allclose(
                        results[wid][it], expected,
                        err_msg=f"worker {wid} round {it}")
        finally:
            mv.MV_ShutDown()

    def test_sync_finish_train_drains(self):
        """Uneven final state: FinishTrain must drain cached messages so
        shutdown doesn't hang (reference server.cpp:188-211)."""
        import multiverso_tpu as mv
        mv.MV_Init(["-num_workers=2", "-sync=true"])
        try:
            table = mv.MV_CreateTable(ArrayTableOption(size=4))
            done = threading.Event()

            def fast_worker():
                from multiverso_tpu.zoo import Zoo
                with Zoo.Get().worker_context(0):
                    table.Add(np.ones(4, np.float32), AddOption(worker_id=0))
                    table.Get(option=GetOption(worker_id=0))
                    # runs ahead: a second add that worker 1 never matches
                    table.AddAsyncHandle(np.ones(4, np.float32),
                                         AddOption(worker_id=0))
                done.set()

            t = threading.Thread(target=fast_worker)
            t.start()
            from multiverso_tpu.zoo import Zoo
            with Zoo.Get().worker_context(1):
                table.Add(np.ones(4, np.float32), AddOption(worker_id=1))
                table.Get(option=GetOption(worker_id=1))
            t.join(timeout=30)
            assert done.is_set()
        finally:
            mv.MV_ShutDown()  # FinishTrain drains the cached 2nd add


class TestAsyncServer:
    def test_async_multi_worker(self):
        """Async mode: adds land in arrival order, total is still exact after
        all workers finish (ASGD semantics, reference server.cpp:23-58)."""
        import multiverso_tpu as mv
        mv.MV_Init(["-num_workers=3"])
        try:
            table = mv.MV_CreateTable(ArrayTableOption(size=16))

            def worker(wid):
                from multiverso_tpu.zoo import Zoo
                with Zoo.Get().worker_context(wid):
                    for _ in range(10):
                        table.Add(np.ones(16, np.float32),
                                  AddOption(worker_id=wid))

            threads = [threading.Thread(target=worker, args=(w,))
                       for w in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            np.testing.assert_allclose(table.Get(), 30.0)
        finally:
            mv.MV_ShutDown()


class TestAggregate:
    def test_allreduce_sum(self):
        """MV_Aggregate(&a,1) == sum over workers
        (reference Test/test_allreduce.cpp:11-20 with -ma)."""
        import multiverso_tpu as mv
        mv.MV_Init(["-num_workers=4", "-ma=true"])
        try:
            outs = [None] * 4

            def worker(wid):
                from multiverso_tpu.zoo import Zoo
                with Zoo.Get().worker_context(wid):
                    data = np.array([1.0, float(wid)], np.float64)
                    mv.MV_Aggregate(data)
                    outs[wid] = data

            threads = [threading.Thread(target=worker, args=(w,))
                       for w in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            for wid in range(4):
                np.testing.assert_allclose(outs[wid], [4.0, 0 + 1 + 2 + 3])
        finally:
            mv.MV_ShutDown()

    def test_ma_mode_has_no_server(self):
        import multiverso_tpu as mv
        from multiverso_tpu.utils.log import FatalError
        mv.MV_Init(["-ma=true"])
        try:
            with pytest.raises(FatalError):
                mv.MV_CreateTable(ArrayTableOption(size=4))
        finally:
            mv.MV_ShutDown()

    def test_device_allreduce(self):
        """psum path over the 8-device test mesh."""
        import jax.numpy as jnp
        from multiverso_tpu.parallel.allreduce import device_allreduce
        from multiverso_tpu.parallel.mesh import build_mesh
        mesh = build_mesh()
        n = mesh.shape["server"]
        x = jnp.arange(n * 4, dtype=jnp.float32)
        out = device_allreduce(x, mesh)
        # psum of shards = sum over shards, broadcast
        expected = np.asarray(x).reshape(n, 4).sum(axis=0)
        np.testing.assert_allclose(np.asarray(out), expected)


class TestBarrier:
    def test_barrier_across_workers(self):
        import multiverso_tpu as mv
        mv.MV_Init(["-num_workers=3"])
        try:
            order = []
            lock = threading.Lock()

            def worker(wid):
                from multiverso_tpu.zoo import Zoo
                with Zoo.Get().worker_context(wid):
                    with lock:
                        order.append(("pre", wid))
                    mv.MV_Barrier()
                    with lock:
                        order.append(("post", wid))

            threads = [threading.Thread(target=worker, args=(w,))
                       for w in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            pres = [i for i, (p, _) in enumerate(order) if p == "pre"]
            posts = [i for i, (p, _) in enumerate(order) if p == "post"]
            assert max(pres) < min(posts)
        finally:
            mv.MV_ShutDown()


class TestAddCoalescing:
    """The async engine's window merges queued Adds into one dispatch
    (ProcessAddRun) and dedups identical Gets — invisible to callers:
    accumulation semantics, error routing, and result ownership hold."""

    def test_burst_adds_accumulate_exactly(self, mv_env):
        import multiverso_tpu as mv
        from multiverso_tpu.tables import MatrixTableOption
        rng = np.random.default_rng(5)
        table = mv_env.MV_CreateTable(
            MatrixTableOption(num_rows=500, num_cols=4))
        oracle = np.zeros((500, 4), np.float32)
        # fire-and-forget bursts queue back-to-back -> merged windows with
        # heavy cross-batch duplicate ids
        for burst in range(6):
            for j in range(7):
                ids = rng.choice(500, 40, replace=False).astype(np.int32)
                deltas = rng.standard_normal((40, 4)).astype(np.float32)
                table.AddFireForget(deltas, row_ids=ids)
                np.add.at(oracle, ids, deltas)
            got = table.GetRows(np.arange(500, dtype=np.int32))
            np.testing.assert_allclose(got, oracle, rtol=1e-4, atol=1e-5)

    def test_burst_with_sgd_updater(self):
        import multiverso_tpu as mv
        from multiverso_tpu.tables import MatrixTableOption
        mv.MV_Init(["-num_workers=1", "-updater_type=sgd"])
        try:
            table = mv.MV_CreateTable(
                MatrixTableOption(num_rows=64, num_cols=3))
            oracle = np.zeros((64, 3), np.float32)
            rng = np.random.default_rng(6)
            for j in range(5):
                ids = rng.choice(64, 16, replace=False).astype(np.int32)
                deltas = rng.standard_normal((16, 3)).astype(np.float32)
                table.AddFireForget(deltas, row_ids=ids)
                np.subtract.at(oracle, ids, deltas)   # sgd: data -= delta
            got = table.Get()
            np.testing.assert_allclose(got, oracle, rtol=1e-4, atol=1e-5)
        finally:
            mv.MV_ShutDown()

    def test_deduped_gets_are_isolated(self, mv_env):
        from multiverso_tpu.tables import MatrixTableOption
        table = mv_env.MV_CreateTable(
            MatrixTableOption(num_rows=32, num_cols=2))
        ids = np.arange(8, dtype=np.int32)
        table.AddRows(ids, np.ones((8, 2), np.float32))
        handles = [table.GetAsyncHandle(row_ids=ids) for _ in range(4)]
        results = [table.Wait(h) for h in handles]
        # a writable result may be mutated without leaking into the
        # others; a read-only one (a device-buffer view — the normal Get
        # semantics) is isolated by immutability
        for r in results:
            np.testing.assert_allclose(r, 1.0)
        mutated = False
        for r in results:
            if r.flags.writeable:
                r[:] = -99.0
                mutated = True
                break
        if mutated:
            assert sum(np.allclose(r, -99.0) for r in results) == 1

    def test_bad_add_in_burst_reports_error(self, mv_env):
        from multiverso_tpu.tables import MatrixTableOption
        from multiverso_tpu.utils.log import FatalError
        table = mv_env.MV_CreateTable(
            MatrixTableOption(num_rows=16, num_cols=2))
        ids = np.arange(4, dtype=np.int32)
        good = table.AddAsyncHandle(np.ones((4, 2), np.float32), row_ids=ids)
        bad = table.AddAsyncHandle(
            np.ones((1, 2), np.float32),
            row_ids=np.array([99], np.int32))   # out of range
        table.Wait(good)
        with pytest.raises(FatalError):
            table.Wait(bad)
        np.testing.assert_allclose(table.GetRows(ids), 1.0)

    def test_sparse_dirty_bits_survive_merged_adds(self):
        """SparseMatrixTable inherits ProcessAddRun; the merged path must
        still fire the freshness-bit bookkeeping per payload, or other
        workers' Gets silently ship stale rows."""
        import multiverso_tpu as mv
        from multiverso_tpu.tables import SparseMatrixTableOption
        from multiverso_tpu.updaters.base import AddOption, GetOption
        mv.MV_Init(["-num_workers=2"])
        try:
            table = mv.MV_CreateTable(SparseMatrixTableOption(
                num_rows=100, num_cols=3))
            ids_a = np.array([3, 7], np.int32)
            ids_b = np.array([7, 50], np.int32)
            # two fire-and-forget adds queue back-to-back -> one window
            table.AddAsyncHandle(np.ones((2, 3), np.float32), row_ids=ids_a,
                                 option=AddOption(worker_id=0))
            table.AddFireForget(np.ones((2, 3), np.float32), row_ids=ids_b,
                                option=AddOption(worker_id=0))
            got_ids, rows = table.Get(GetOption(worker_id=1))
            assert sorted(got_ids.tolist()) == [3, 7, 50], got_ids
            lookup = dict(zip(got_ids.tolist(), rows))
            np.testing.assert_allclose(lookup[7], 2.0)
            np.testing.assert_allclose(lookup[3], 1.0)
        finally:
            mv.MV_ShutDown()
