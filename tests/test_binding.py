"""Binding-surface tests — counterpart of reference
binding/python/multiverso/tests/test_multiverso.py (array/matrix
accumulation invariants, master-init convention, param-manager sync loop).
"""

import threading

import numpy as np
import pytest


@pytest.fixture()
def binding():
    import multiverso_tpu.binding as mv
    mv.init()
    yield mv
    mv.shutdown()


class TestBindingApi:
    def test_world_introspection(self, binding):
        assert binding.workers_num() == 1
        assert binding.worker_id() == 0
        assert binding.is_master_worker()

    def test_array_handler_accumulation(self, binding):
        # reference test_multiverso.py:26-34
        t = binding.ArrayTableHandler(100)
        delta = np.arange(100, dtype=np.float32)
        for _ in range(3):
            t.add(delta, sync=True)
        np.testing.assert_allclose(t.get(), 3 * delta)

    def test_array_init_value_master(self, binding):
        init = np.full(10, 7.0, np.float32)
        t = binding.ArrayTableHandler(10, init_value=init)
        np.testing.assert_allclose(t.get(), init)

    def test_matrix_handler_rows(self, binding):
        # reference test_multiverso.py:46-71
        t = binding.MatrixTableHandler(20, 5)
        whole = np.ones((20, 5), np.float32)
        t.add(whole, sync=True)
        np.testing.assert_allclose(t.get(), 1.0)
        t.add(np.ones((3, 5), np.float32), row_ids=[1, 5, 19], sync=True)
        rows = t.get(row_ids=[1, 5, 19, 0])
        np.testing.assert_allclose(rows[:3], 2.0)
        np.testing.assert_allclose(rows[3], 1.0)

    def test_async_add_visible_after_barrier_get(self, binding):
        t = binding.ArrayTableHandler(10)
        t.add(np.ones(10, np.float32))           # async
        t.add(np.ones(10, np.float32), sync=True)  # sync flushes behind it
        np.testing.assert_allclose(t.get(), 2.0)


class TestSharedTableManagers:
    def test_in_process_workers_share_one_table(self):
        """Two worker threads with private replicas + ONE shared table:
        delta-syncs merge both workers' progress (the examples/torch_asgd
        pattern; multi-process jobs create one handler per process
        instead)."""
        import multiverso_tpu as mvt
        from multiverso_tpu.binding import ArrayTableHandler
        from multiverso_tpu.binding.param_manager import MVModelParamManager
        import threading
        mvt.MV_Init(["-num_workers=2"])
        try:
            init = np.zeros(4, np.float32)
            shared = ArrayTableHandler(4, init_value=init)
            merged = {}

            def worker(wid):
                with mvt.MV_WorkerContext(wid):
                    state = {"v": init.copy()}
                    mgr = MVModelParamManager(
                        lambda: state["v"],
                        lambda vec: state.update(v=vec.copy()),
                        table=shared)
                    state["v"] = state["v"] + (wid + 1)  # local progress
                    mgr.sync_all_param()
                    mvt.MV_Barrier()      # both pushes landed
                    mgr.sync_all_param()  # second sync pulls peer's delta
                    merged[wid] = state["v"].copy()

            ts = [threading.Thread(target=worker, args=(w,))
                  for w in range(2)]
            [t.start() for t in ts]
            [t.join(timeout=60) for t in ts]
            assert not any(t.is_alive() for t in ts)
            # both deltas (1 and 2) land exactly once
            np.testing.assert_allclose(merged[0], 3.0)
            np.testing.assert_allclose(merged[1], 3.0)
        finally:
            mvt.MV_ShutDown()


class TestNetBindConnect:
    """MV_NetBind/MV_NetConnect: the launcher-free bring-up path
    (reference zmq_net.h:64-110 MPI-free deployment) — declarations feed
    jax.distributed at the next MV_Init. Single-process tier checks the
    declaration contract; the 2-process wiring is driven end-to-end in
    test_multihost.py::TestTwoProcessNetBind."""

    def teardown_method(self):
        from multiverso_tpu.parallel import multihost
        multihost.net_reset()

    def test_declaration_contract(self):
        import multiverso_tpu as mv
        # connect before bind is an error
        assert mv.MV_NetConnect([0], ["127.0.0.1:5555"]) == -1
        assert mv.MV_NetBind(0, "127.0.0.1:5555") == 0
        # world must include this rank and rank 0
        assert mv.MV_NetConnect([1], ["127.0.0.1:6666"]) == -1
        assert mv.MV_NetConnect([0, 1], ["127.0.0.1:5555"]) == -1  # ragged
        assert mv.MV_NetConnect(
            [0, 1], ["127.0.0.1:5555", "127.0.0.1:6666"]) == 0

    def test_bad_bind_rejected(self):
        import multiverso_tpu as mv
        assert mv.MV_NetBind(-1, "127.0.0.1:5555") == -1
        assert mv.MV_NetBind(0, "") == -1
        assert mv.MV_NetBind("x", "127.0.0.1:5555") == -1
        assert mv.MV_NetConnect([0, "x"], ["a", "b"]) == -1  # malformed -> -1

    def test_rebind_invalidates_world(self):
        """Re-declaring identity after a validated world requires a fresh
        connect — the old validation was against the old identity."""
        import multiverso_tpu as mv
        from multiverso_tpu.parallel import multihost
        assert mv.MV_NetBind(0, "127.0.0.1:5555") == 0
        assert mv.MV_NetConnect(
            [0, 1], ["127.0.0.1:5555", "127.0.0.1:6666"]) == 0
        assert mv.MV_NetBind(7, "127.0.0.1:7777") == 0
        assert multihost._net_world is None


class TestParamManager:
    def test_jax_param_manager_sync(self, binding):
        from multiverso_tpu.binding.param_manager import JaxParamManager
        params = {"w": np.ones((4, 3), np.float32),
                  "b": np.zeros(3, np.float32)}
        mgr = JaxParamManager(params)
        # local training step: w += 0.5
        trained = {"w": params["w"] + 0.5, "b": params["b"]}
        merged = mgr.sync(trained)
        np.testing.assert_allclose(np.asarray(merged["w"]), 1.5)
        np.testing.assert_allclose(np.asarray(merged["b"]), 0.0)

    def test_jax_manager_shared_table_two_workers(self):
        """The flax ASGD pattern (examples/flax_asgd.py): two worker
        threads share ONE table through JaxParamManager(table=) +
        SyncCallback; every worker's deltas land on the shared table and
        each final pull bounds between its own contribution and the
        server total (ASGD: only the server state is deterministic)."""
        import multiverso_tpu as mvc
        import multiverso_tpu.binding as mv
        from multiverso_tpu.binding.param_manager import (JaxParamManager,
                                                          SyncCallback)
        import threading
        mv.init(args=["-num_workers=2"])
        try:
            init = np.zeros(6, np.float32)  # flat size of the (2,3) pytree
            shared = mv.ArrayTableHandler(init.size, init_value=init)
            finals = {}

            def worker(wid):
                with mvc.MV_WorkerContext(wid):
                    mgr = JaxParamManager({"w": np.zeros((2, 3), np.float32)},
                                          table=shared)
                    cb = SyncCallback(mgr, freq=2)
                    params = mgr.params()
                    for _ in range(4):  # 4 batches -> 2 syncs via callback
                        params = {"w": params["w"] + (wid + 1)}
                        mgr.update(params)
                        cb.on_batch_end()
                        params = mgr.params()
                    cb.on_train_end()
                    finals[wid] = np.asarray(mgr.params()["w"]).copy()

            ts = [threading.Thread(target=worker, args=(w,)) for w in (0, 1)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
            assert all(not t.is_alive() for t in ts)
            # both workers pushed 4 increments each: +1*4 and +2*4 = +12
            server = np.asarray(shared.get()).reshape(2, 3)
            np.testing.assert_allclose(server, 12.0)
            for wid in (0, 1):
                # each worker's final pull holds its own full contribution
                # plus whatever subset of the peer's had landed by then
                # (ASGD: the last puller sees everything, the first may
                # not — only the server total is deterministic)
                own = 4.0 * (wid + 1)
                assert np.all(finals[wid] >= own - 1e-5), (wid, finals[wid])
                assert np.all(finals[wid] <= 12.0 + 1e-5), (wid, finals[wid])
        finally:
            mv.shutdown()

    def test_torch_param_manager_sync(self, binding):
        torch = pytest.importorskip("torch")
        model = torch.nn.Linear(4, 2)
        from multiverso_tpu.binding.param_manager import TorchParamManager
        mgr = TorchParamManager(model)
        before = model.weight.detach().numpy().copy()
        with torch.no_grad():
            model.weight += 1.0
        mgr.sync_all_param()
        after = model.weight.detach().numpy()
        np.testing.assert_allclose(after, before + 1.0, rtol=1e-6)

    def test_delta_trick_multi_worker(self):
        """Two workers train divergently between syncs; after both sync, the
        server holds base + delta0 + delta1 (reference sharedvar.py:37-49)."""
        import multiverso_tpu.binding as mv
        mv.init(args=["-num_workers=2"])
        try:
            t = mv.ArrayTableHandler(4, init_value=np.zeros(4, np.float32))
            results = {}
            # both deltas are taken against the base BEFORE either lands:
            # without the barrier a worker's second get() could see the
            # other's add and push 1 - 2, a race of the test's own making
            both_read = threading.Barrier(2)

            def worker(wid):
                from multiverso_tpu.zoo import Zoo
                with Zoo.Get().worker_context(wid):
                    local = t.get().copy()
                    local += (wid + 1)  # local training
                    delta = local - t.get()
                    both_read.wait(timeout=30)
                    t.add(delta, sync=True)
                    results[wid] = True

            ts = [threading.Thread(target=worker, args=(w,)) for w in range(2)]
            for th in ts:
                th.start()
            for th in ts:
                th.join(timeout=30)
            assert results == {0: True, 1: True}
            np.testing.assert_allclose(t.get(), 3.0)
        finally:
            mv.shutdown()

    def test_sync_callback_freq(self, binding):
        """SyncCallback syncs every ``freq`` batches + once at train end
        (reference keras_ext/callbacks.py:36-39)."""
        from multiverso_tpu.binding.param_manager import (JaxParamManager,
                                                          SyncCallback)
        params = {"w": np.zeros(4, np.float32)}
        mgr = JaxParamManager(params)
        cb = SyncCallback(mgr, freq=2)
        syncs = []
        orig = mgr.sync_all_param
        mgr.sync_all_param = lambda: (syncs.append(1), orig())[1]
        for _ in range(5):
            cb.on_batch_end()
        assert len(syncs) == 2          # batches 2 and 4
        cb.on_train_end()
        assert len(syncs) == 3


class TestForeignBindings:
    """The Lua (FFI cdef) and C# (DllImport) bindings ship source-only —
    LuaJIT and .NET are not in this image — so validate them at the ABI
    level: every symbol they declare must exist in the built shared
    library and be declared in native/include/mvt/c_api.h."""

    @pytest.fixture(scope="class")
    def repo_root(self):
        import os
        return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    @pytest.fixture(scope="class")
    def native_lib(self):
        from multiverso_tpu.native import lib
        handle = lib()
        if handle is None:
            pytest.skip("native library unavailable")
        return handle

    @staticmethod
    def _declared(path, pattern):
        import re
        with open(path) as f:
            return set(re.findall(pattern, f.read()))

    @pytest.fixture(scope="class")
    def c_api_names(self, repo_root):
        import os
        header = os.path.join(repo_root, "native", "include", "mvt",
                              "c_api.h")
        return self._declared(header, r"\b(MV_\w+)\s*\(")

    def _check_against_abi(self, names, c_api_names, native_lib):
        assert names, "no MV_* declarations found"
        for name in names:
            assert name in c_api_names, f"{name} not in c_api.h"
            assert hasattr(native_lib, name), f"{name} missing from .so"

    def test_lua_cdef_symbols(self, repo_root, native_lib, c_api_names):
        import os
        lua = os.path.join(repo_root, "binding", "lua", "multiverso",
                           "init.lua")
        self._check_against_abi(self._declared(lua, r"\b(MV_\w+)\s*\("),
                                c_api_names, native_lib)

    def test_lua_handler_calls_are_declared(self, repo_root):
        """Every mv.C.<fn> call in the handler files is covered by the
        single cdef block in init.lua."""
        import os
        base = os.path.join(repo_root, "binding", "lua", "multiverso")
        cdef_names = self._declared(os.path.join(base, "init.lua"),
                                    r"\b(MV_\w+)\s*\(")
        for fname in ("ArrayTableHandler.lua", "MatrixTableHandler.lua"):
            calls = self._declared(os.path.join(base, fname),
                                   r"mv\.C\.(MV_\w+)")
            assert calls <= cdef_names, f"{fname}: {calls - cdef_names}"

    def test_csharp_dllimport_symbols(self, repo_root, native_lib,
                                      c_api_names):
        import os
        cs = os.path.join(repo_root, "binding", "csharp",
                          "MultiversoTPU.cs")
        self._check_against_abi(
            self._declared(cs, r"extern\s+\w+\s+(MV_\w+)\s*\("),
            c_api_names, native_lib)


class TestSharedVar:
    """Per-variable mv_shared surface (reference theano_ext/sharedvar.py)."""

    def test_mv_sync_delta_trick(self, binding):
        from multiverso_tpu.binding import sharedvar as sv
        var = sv.mv_shared(np.zeros((2, 3), np.float32))
        assert var.get_value().shape == (2, 3)
        # local training step: value drifts by +1 everywhere
        var.set_value(var.get_value() + 1.0)
        var.mv_sync()
        np.testing.assert_allclose(var.get_value(), np.ones((2, 3)))
        # second drift merges additively on the server
        var.set_value(var.get_value() + 2.0)
        var.mv_sync()
        np.testing.assert_allclose(var.get_value(), 3 * np.ones((2, 3)))

    def test_sync_all_registry(self, binding):
        from multiverso_tpu.binding import sharedvar as sv
        sv.mv_shared.shared_vars.clear()
        a = sv.mv_shared(np.zeros(4, np.float32))
        b = sv.mv_shared(np.full(4, 5.0, np.float32))
        a.set_value(a.get_value() + 1.0)
        sv.sync_all_mv_shared_vars()
        np.testing.assert_allclose(a.get_value(), 1.0)
        np.testing.assert_allclose(b.get_value(), 5.0)

    def test_master_initializes(self, binding):
        """Init value lands exactly once even though every worker adds
        (worker 0 contributes the value, the rest zeros)."""
        from multiverso_tpu.binding import sharedvar as sv
        init = np.arange(6, dtype=np.float32).reshape(2, 3)
        var = sv.mv_shared(init)
        np.testing.assert_allclose(var.get_value(), init)

    def test_attribute_forwarding(self, binding):
        from multiverso_tpu.binding import sharedvar as sv
        box = sv.SharedArray(np.zeros(2, np.float32))
        box.custom_tag = "hello"
        var = sv.MVSharedVariable(box)
        assert var.custom_tag == "hello"
