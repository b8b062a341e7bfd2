"""Multi-host layer (parallel/multihost.py).

Two tiers here, mirroring the reference's split between in-process
fixtures and mpirun-launched integration tests (SURVEY.md §4.2):

* single-process behavior — the 1-process degradations (identity / no-op),
  flag gating, and the cross_reduce hook the Zoo wires into MV_Aggregate's
  rendezvous;
* a REAL 2-process integration test — two subprocesses joined through
  ``jax.distributed`` with a local coordinator (the moral equivalent of
  ``mpirun -n 2 multiverso.test array``, reference Test/main.cpp), driving
  PS tables with *divergent per-process payloads* and checkpointing.
"""

import os
import socket
import subprocess
import sys
import threading

import numpy as np
import pytest


def run_n_process(child_src: str, tmp_path, *child_args, nproc: int = 2,
                  timeout: int = 280, expect: str = "OK") -> list:
    """Launch ``nproc`` jax.distributed subprocesses running ``child_src``
    (argv: rank, coordinator-port, *child_args); assert all exit 0 and
    print ``child <rank> ... {expect}``. Returns all outputs."""
    child = tmp_path / "child.py"
    child.write_text(child_src)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ, PYTHONPATH=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    procs = [subprocess.Popen(
        [sys.executable, str(child), str(r), str(port),
         *[str(a) for a in child_args]],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for r in range(nproc)]
    outs = []
    for r, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            out, _ = p.communicate()
            pytest.fail(f"{nproc}-process run hung:\n{out[-2000:]}")
        assert p.returncode == 0, f"rank {r} failed:\n{out[-2000:]}"
        assert f"child {r}" in out and expect in out, out[-500:]
        outs.append(out)
    return outs


def run_two_process(child_src: str, tmp_path, *child_args,
                    timeout: int = 280, expect: str = "OK") -> list:
    return run_n_process(child_src, tmp_path, *child_args, nproc=2,
                         timeout=timeout, expect=expect)


class TestSingleProcessDegradation:
    def test_identity_ops(self):
        from multiverso_tpu.parallel import multihost as mh
        assert mh.process_count() == 1
        assert mh.process_index() == 0
        mh.host_barrier()  # no-op, must not raise
        x = np.arange(6, dtype=np.float32)
        assert mh.host_allreduce_sum(x) is x
        assert mh.broadcast_from_master(x) is x

    def test_auto_mode_stays_off_without_env(self, monkeypatch):
        from multiverso_tpu.parallel import multihost as mh
        from multiverso_tpu.utils.configure import SetCMDFlag
        for var in ("JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS",
                    "MEGASCALE_COORDINATOR_ADDRESS"):
            monkeypatch.delenv(var, raising=False)
        SetCMDFlag("multihost", "auto")
        assert mh.maybe_initialize() is False

    def test_off_mode_never_initializes(self, monkeypatch):
        from multiverso_tpu.parallel import multihost as mh
        from multiverso_tpu.utils.configure import SetCMDFlag
        monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "localhost:1234")
        SetCMDFlag("multihost", "off")
        try:
            assert mh.maybe_initialize() is False
        finally:
            SetCMDFlag("multihost", "auto")

    def test_zoo_single_process_identity(self, mv_env):
        from multiverso_tpu.zoo import Zoo
        assert Zoo.Get().size == 1
        assert Zoo.Get().rank == 0


_CHILD = r'''
import os, sys
rank, port, ckpt = int(sys.argv[1]), sys.argv[2], sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.tables import (ArrayTableOption, KVTableOption,
                                   MatrixTableOption)

mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2"])
assert mv.MV_Size() == 2 and mv.MV_Rank() == rank

# array: per-process deltas of one collective Add SUM (reference semantics)
arr = mv.MV_CreateTable(ArrayTableOption(size=16))
arr.Add(np.full(16, float(rank + 1), np.float32))
assert np.allclose(arr.Get(), 3.0)

# matrix: divergent row sets; both processes' adds land, each process
# reads its own row set out of the collective Get
mat = mv.MV_CreateTable(MatrixTableOption(num_rows=32, num_cols=4))
my_rows = np.array([rank, 10 + rank], np.int32)
mat.AddRows(my_rows, np.full((2, 4), float(rank + 1), np.float32))
rows = mat.GetRows(np.array([0, 1, 10, 11], np.int32))
assert np.allclose(rows[[0, 2]], 1.0) and np.allclose(rows[[1, 3]], 2.0)
assert np.allclose(mat.GetRows(my_rows), float(rank + 1))

# kv: divergent key sets; slot index stays consistent on every host
kv = mv.MV_CreateTable(KVTableOption())
kv.Add(np.array([100 + rank, 500], np.int64),
       np.array([1.0, 1.0], np.float32))
assert np.allclose(kv.Get(np.array([100, 101, 500], np.int64)),
                   [1.0, 1.0, 2.0])

# checkpoint: collective serialize, process-0 write, everyone reloads
mv.MV_SaveCheckpoint(ckpt)
arr.Add(np.ones(16, np.float32))           # diverge (collectively)
mv.MV_LoadCheckpoint(ckpt)
assert np.allclose(arr.Get(), 3.0)
mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} OK", flush=True)
'''


class TestTwoProcessIntegration:
    def test_ps_tables_across_two_processes(self, tmp_path):
        run_two_process(_CHILD, tmp_path, f"file://{tmp_path}/ckpt.mvt")


_SYNC_CHILD = r'''
import os, sys
rank, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.tables import ArrayTableOption

mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2", "-sync=true"])
arr = mv.MV_CreateTable(ArrayTableOption(size=8))
for i in range(4):
    arr.Add(np.full(8, float(rank + 1), np.float32))
    g = arr.Get()
    # BSP across processes: round i sees BOTH processes' adds (1+2 per
    # round) and every process's i-th Get is identical
    assert np.allclose(g, 3.0 * (i + 1)), (i, g)
mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} SYNC OK", flush=True)
'''


class TestTwoProcessSync:
    def test_bsp_guarantee_across_processes(self, tmp_path):
        """The SyncServer BSP guarantee (reference server.cpp:60-67) holds
        across jax.distributed processes: per-process engines make
        identical defer/drain decisions because the merged collective verb
        stream is identical everywhere."""
        run_two_process(_SYNC_CHILD, tmp_path, expect="SYNC OK")


_NETBIND_CHILD = r'''
import os, sys
rank, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv

# launcher-free bring-up: the world is declared through the two reference
# net verbs ONLY (no -dist_* flags, no env) — rank 0's endpoint is the
# coordinator jax.distributed rendezvouses on
endpoints = [f"127.0.0.1:{port}", f"127.0.0.1:{int(port) + 1}"]
assert mv.MV_NetBind(rank, endpoints[rank]) == 0
assert mv.MV_NetConnect([0, 1], endpoints) == 0
mv.MV_Init([])
assert mv.MV_Size() == 2 and mv.MV_Rank() == rank

from multiverso_tpu.tables import ArrayTableOption
arr = mv.MV_CreateTable(ArrayTableOption(size=8))
arr.Add(np.full(8, float(rank + 1), np.float32))
assert np.allclose(arr.Get(), 3.0)
mv.MV_Barrier()
mv.MV_ShutDown()
mv.MV_NetFinalize()   # reference MV_NetFinalize: transport torn down
print(f"child {rank} NETBIND OK", flush=True)
'''


class TestTwoProcessNetBind:
    def test_world_wired_through_net_verbs_only(self, tmp_path):
        """MV_NetBind + MV_NetConnect alone bring up the 2-process world
        (reference MPI-free ZMQ deployment, zmq_net.h:64-110)."""
        run_two_process(_NETBIND_CHILD, tmp_path, expect="NETBIND OK")


_MACHINE_FILE_CHILD = r'''
import os, sys
rank, port, mf = int(sys.argv[1]), sys.argv[2], sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.tables import ArrayTableOption

# world from the hosts file (reference ZMQ -machine_file, line N = rank N);
# same-host processes disambiguate identity with -dist_rank exactly like
# the reference's ambiguous local-IP match would require
mv.MV_Init([f"-machine_file={mf}", f"-dist_rank={rank}"])
assert mv.MV_Size() == 2 and mv.MV_Rank() == rank
arr = mv.MV_CreateTable(ArrayTableOption(size=4))
arr.Add(np.full(4, float(rank + 1), np.float32))
assert np.allclose(arr.Get(), 3.0)
mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} MACHINEFILE OK", flush=True)
'''


class TestMachineFile:
    def test_parse_and_port_fill(self, tmp_path):
        from multiverso_tpu.parallel import multihost
        from multiverso_tpu.utils.configure import SetCMDFlag
        mf = tmp_path / "hosts"
        mf.write_text("# cluster\nhost-a:7000\n\nhost-b\n")
        from multiverso_tpu.utils.configure import GetFlag
        saved = GetFlag("port")
        SetCMDFlag("port", 6000)
        try:
            assert multihost._parse_machine_file(str(mf)) == [
                "host-a:7000", "host-b:6000"]
            # IPv6: bracketed keeps its port, bare literal gets bracketed
            mf.write_text("[::1]:7000\nfe80::abcd\n")
            assert multihost._parse_machine_file(str(mf)) == [
                "[::1]:7000", "[fe80::abcd]:6000"]
            # empty / missing files fail loudly (never silent 1-process)
            mf.write_text("# only comments\n")
            with pytest.raises(Exception):
                multihost._parse_machine_file(str(mf))
            with pytest.raises(Exception):
                multihost._parse_machine_file(str(mf) + ".nope")
        finally:
            SetCMDFlag("port", saved)

    def test_local_rank_match(self, tmp_path):
        from multiverso_tpu.parallel import multihost
        # unique local line -> matched; two local lines -> ambiguous (None)
        assert multihost._match_local_rank(
            ["10.255.255.1:7000", "127.0.0.1:7001"]) == 1
        assert multihost._match_local_rank(
            ["127.0.0.1:7000", "127.0.0.1:7001"]) is None

    def test_two_process_world_from_machine_file(self, tmp_path):
        mf = tmp_path / "hosts"
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        mf.write_text(f"127.0.0.1:{port}\n127.0.0.1:{port + 1}\n")
        run_two_process(_MACHINE_FILE_CHILD, tmp_path, str(mf),
                        expect="MACHINEFILE OK")


_SPARSE_CHILD = r'''
import os, sys
rank, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.tables import SparseMatrixTableOption

mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2"])
t = mv.MV_CreateTable(SparseMatrixTableOption(num_rows=16, num_cols=3))

# collective Add, divergent row sets: rank0 pushes rows [1,3] (+1), rank1
# pushes [5,7] (+2). Freshness oracle (one shared server, global workers
# gwid=rank): each pusher keeps its OWN rows fresh, the peer's rows stale.
my_ids = np.array([1, 3] if rank == 0 else [5, 7], np.int32)
t.AddRows(my_ids, np.full((2, 3), float(rank + 1), np.float32))

ids, rows = t.Get()
expect_ids = [5, 7] if rank == 0 else [1, 3]
expect_val = 2.0 if rank == 0 else 1.0
assert ids.tolist() == expect_ids, (rank, ids)
assert np.allclose(rows, expect_val), (rank, rows)

# everything fresh now -> protocol still ships row 0
ids, rows = t.Get()
assert ids.tolist() == [0] and np.allclose(rows, 0.0), (rank, ids, rows)

# second divergent Add: rank0 re-pushes row 5, rank1 pushes row 9
t.AddRows(np.array([5] if rank == 0 else [9], np.int32),
          np.full((1, 3), float(rank + 1), np.float32))
ids, rows = t.Get()
if rank == 0:
    assert ids.tolist() == [9] and np.allclose(rows, 2.0), (ids, rows)
else:
    assert ids.tolist() == [5] and np.allclose(rows, 3.0), (ids, rows)

# row-set-restricted Get: only the stale subset of the requested ids ships
t.AddRows(np.array([2] if rank == 0 else [12], np.int32),
          np.full((1, 3), 1.0, np.float32))
ids, rows = t.GetRows(np.array([2, 3, 12], np.int32))
expect_ids = [12] if rank == 0 else [2]
assert ids.tolist() == expect_ids, (rank, ids)

# whole-table collective Add marks everything stale for everyone (each
# keeper is un-marked only by its own part); both fetch all 16 rows
t.Add(np.ones((16, 3), np.float32))
ids, rows = t.Get()
assert len(ids) == 16, (rank, ids)
mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} SPARSE OK", flush=True)
'''


class TestTwoProcessSparse:
    def test_dirty_row_protocol_across_processes(self, tmp_path):
        """The per-worker dirty-row protocol holds across jax.distributed
        processes (reference sparse_matrix_table.cpp:200-259 is inherently
        multi-node): freshness bits are replicated per process, keyed by
        global worker id, and kept in lockstep by applying every process's
        allgathered (worker, rows) parts in rank order — each interleaved
        Get ships exactly the single-shared-server oracle's stale set."""
        run_two_process(_SPARSE_CHILD, tmp_path, expect="SPARSE OK")


_DEVICE_PLANE_CHILD = r'''
import os, sys
rank, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
import multiverso_tpu as mv
from multiverso_tpu.tables import (ArrayTableOption, KVTableOption,
                                   MatrixTableOption)
from multiverso_tpu.updaters.base import AddOption

mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2"])
opt = AddOption().as_jnp()

# -- matrix: eager multi-process device plane -------------------------------
# divergent per-process batches WITH a cross-process duplicate (row 20):
# the parts round merges on device; dedup combines row 20's deltas by sum
mat = mv.MV_CreateTable(MatrixTableOption(num_rows=32, num_cols=4))
srv = mat.server()
my_ids = np.array([rank, 10 + rank, 20], np.int32)
srv.device_apply_rows(my_ids, np.full((3, 4), float(rank + 1), np.float32))
rows = mat.GetRows(np.array([0, 1, 10, 11, 20], np.int32))
assert np.allclose(rows[[0, 2]], 1.0), rows
assert np.allclose(rows[[1, 3]], 2.0), rows
assert np.allclose(rows[4], 3.0), rows  # 1.0 + 2.0 merged on device
# eager fetch: each process reads its own rows out of one merged round
mine = srv.device_fetch_rows(np.array([10 + rank], np.int32))
assert np.allclose(np.asarray(mine), float(rank + 1)), mine

# -- matrix: scan-style traced parts rounds (fixed bucket) ------------------
for step in range(3):
    gids, gdeltas = srv.device_place_batch(
        np.array([rank, 20], np.int32),
        np.full((2, 4), 1.0, np.float32), bucket=4)
    srv.state = srv._update_rows_parts_j(srv.state, gids, gdeltas, opt)
rows = mat.GetRows(np.array([0, 1, 20], np.int32))
assert np.allclose(rows[0], 1.0 + 3.0), rows   # proc 0's three rounds
assert np.allclose(rows[1], 2.0 + 3.0), rows
assert np.allclose(rows[2], 3.0 + 6.0), rows   # both processes x 3 rounds

# -- kv: multi-process device plane -----------------------------------------
kv = mv.MV_CreateTable(KVTableOption())
ksrv = kv.server()
my_keys = np.array([100 + rank, 500], np.int64)
slots = ksrv.device_slots(my_keys, create=True)   # merges key sets
gslots, gdeltas = ksrv.device_place_slots(
    slots, np.pad(np.ones(2, np.float32), (0, len(slots) - 2)))
vals = ksrv.device_values()
vals = jax.jit(ksrv.device_scatter_add_slots, donate_argnums=(0,))(
    vals, gslots, gdeltas)
ksrv.device_set_values(vals)
got = kv.Get(np.array([100, 101, 500], np.int64))
assert np.allclose(got, [1.0, 1.0, 2.0]), got   # 500 accumulated both
# parts gather: replicated out, each process slices its own range
rep = jax.jit(ksrv.device_gather_slots,
              out_shardings=NamedSharding(ksrv._zoo.mesh_ctx.mesh, P()))(
    ksrv.device_values(), gslots)
local = np.asarray(rep.addressable_data(0))
mine = local[rank * len(slots): rank * len(slots) + 2]
assert np.allclose(mine, [1.0, 2.0]), mine

# -- array: per-process parts delta summed in the traced round --------------
arr = mv.MV_CreateTable(ArrayTableOption(size=16))
asrv = arr.server()
parts = asrv.device_place_parts_delta(
    np.full(16, float(rank + 1), np.float32))
state = jax.jit(asrv.device_update_parts, donate_argnums=(0,))(
    asrv.device_state(), parts, opt)
asrv.device_set_state(state)
assert np.allclose(arr.Get(), 3.0), arr.Get()

mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} DEVICE PLANE OK", flush=True)
'''


class TestTwoProcessDevicePlane:
    """The SPMD multi-process device plane (round-3 top ask): every
    process issues the identical traced round while passing its OWN
    batch as a shard of a global parts array — cross-process duplicate
    ids combine by sum ON DEVICE (ops.dedup_rows), the host plane then
    reads the merged result. Matches the reference's workers-reach-every-
    server-shard deployment (worker.cpp:30-79) with ICI as the wire."""

    def test_device_plane_across_processes(self, tmp_path):
        run_two_process(_DEVICE_PLANE_CHILD, tmp_path,
                        expect="DEVICE PLANE OK")


_LR_CHILD = r'''
import os, sys
rank, port, workdir = int(sys.argv[1]), sys.argv[2], sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.models.logreg.configure import Configure
from multiverso_tpu.models.logreg.logreg import LogReg

os.chdir(workdir)
mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2"])
cfg = Configure(input_size=16, output_size=1, objective_type="sigmoid",
                updater_type="sgd", learning_rate=0.3, train_epoch=3,
                minibatch_size=32, use_ps=True, sync_frequency=2,
                train_file=f"train_{rank}.data", test_file="test.data",
                output_model_file=f"model_{rank}.bin",
                output_file=f"out_{rank}.txt")
lr = LogReg(cfg)
lr.Train()
acc = lr.Test()
np.save(f"W_{rank}.npy", lr.model.weights())
mv.MV_Barrier()
mv.MV_ShutDown()
assert acc > 0.85, acc
print(f"child {rank} LR acc {acc:.3f} OK", flush=True)
'''


class TestTwoProcessLogReg:
    """The BASELINE north star in miniature: the bundled LogisticRegression
    app training DATA-PARALLEL across two jax.distributed processes through
    the parameter server — each process streams a different data shard,
    pushes lr-scaled deltas, pulls every sync_frequency batches. Both
    processes must converge AND hold identical final weights (the PS is the
    single source of truth; merged collective Adds are deterministic)."""

    def test_data_parallel_lr_converges_identically(self, tmp_path):
        rng = np.random.default_rng(0)
        true_w = rng.standard_normal(16).astype(np.float32)

        def write(path, n, seed):
            r = np.random.default_rng(seed)
            X = r.standard_normal((n, 16)).astype(np.float32)
            y = (X @ true_w > 0).astype(int)
            with open(path, "w") as f:
                for lab, row in zip(y, X):
                    f.write(f"{lab} " +
                            " ".join(f"{v:.4f}" for v in row) + "\n")

        write(tmp_path / "train_0.data", 640, 1)
        write(tmp_path / "train_1.data", 640, 2)  # different shard
        write(tmp_path / "test.data", 400, 3)
        run_two_process(_LR_CHILD, tmp_path, tmp_path, expect="LR acc")
        W0 = np.load(tmp_path / "W_0.npy")
        W1 = np.load(tmp_path / "W_1.npy")
        np.testing.assert_array_equal(W0, W1)


_WE_CHILD = r'''
import os, sys
rank, port, workdir = int(sys.argv[1]), sys.argv[2], sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import multiverso_tpu as mv
from multiverso_tpu.models.wordembedding.option import Option
from multiverso_tpu.models.wordembedding.distributed import (
    DistributedWordEmbedding)

os.chdir(workdir)
mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2"])
mode = sys.argv[4] if len(sys.argv) > 4 else ""
# pairs mode shrinks the block so unevenly-sized shards produce UNEQUAL
# block counts (exercising the ragged lockstep protocol)
extra = {"device": ["-device_plane", "1"],
         "pairs": ["-device_pairs", "1", "-data_block_size", "2000"]}.get(
    mode, [])
opt = Option.parse_args([
    "-train_file", f"corpus_{rank}.txt", "-output", f"vectors_{rank}.txt",
    "-size", "16", "-epoch", "2", "-negative", "3", "-min_count", "1",
    "-read_vocab", "vocab.txt", "-data_block_size", "20000",
    "-is_pipeline", "0"] + extra)
dwe = DistributedWordEmbedding(opt)
dwe.run()
mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} WE OK", flush=True)
'''


class TestTwoProcessWordEmbedding:
    """The second bundled app data-parallel across two processes: 4 shared
    embedding/accumulator MatrixTables + the int64 word-count KVTable, each
    process streaming a different corpus shard. Both processes must finish
    and save IDENTICAL embeddings (the PS is the single source of truth)."""

    def test_we_trains_across_two_processes(self, tmp_path):
        rng = np.random.default_rng(0)
        words = [f"w{i}" for i in range(200)]

        def gen(path, seed, sents):
            r = np.random.default_rng(seed)
            with open(path, "w") as f:
                for _ in range(sents):
                    f.write(" ".join(r.choice(words, 10)) + "\n")

        gen(tmp_path / "corpus_0.txt", 1, 800)
        gen(tmp_path / "corpus_1.txt", 2, 800)  # different shard
        with open(tmp_path / "vocab.txt", "w") as f:
            for w in words:
                f.write(f"{w} 100\n")
        run_two_process(_WE_CHILD, tmp_path, tmp_path, expect="WE OK")
        v0 = (tmp_path / "vectors_0.txt").read_text()
        v1 = (tmp_path / "vectors_1.txt").read_text()
        assert v0 == v1, "processes saved different embeddings"

    def test_we_device_plane_across_two_processes(self, tmp_path):
        """-device_plane 1 across two processes: each process's block rows
        merge on device through the parts round (cross-process duplicate
        rows combine by sum, like the host plane's collective merge) and
        the saved embeddings still agree."""
        words = [f"w{i}" for i in range(120)]

        def gen(path, seed, sents):
            r = np.random.default_rng(seed)
            with open(path, "w") as f:
                for _ in range(sents):
                    f.write(" ".join(r.choice(words, 10)) + "\n")

        gen(tmp_path / "corpus_0.txt", 3, 400)
        gen(tmp_path / "corpus_1.txt", 4, 400)
        with open(tmp_path / "vocab.txt", "w") as f:
            for w in words:
                f.write(f"{w} 100\n")
        run_two_process(_WE_CHILD, tmp_path, tmp_path, "device",
                        expect="WE OK")
        v0 = (tmp_path / "vectors_0.txt").read_text()
        v1 = (tmp_path / "vectors_1.txt").read_text()
        assert v0 == v1, "processes saved different embeddings"


class TestCrossReduceHook:
    def test_applied_once_per_round_by_last_thread(self):
        from multiverso_tpu.parallel.allreduce import RendezvousAllreduce
        calls = []

        def cross(buf):
            calls.append(buf.copy())
            return buf * 10  # simulates the cross-host sum

        ar = RendezvousAllreduce(3, cross_reduce=cross)
        outs = {}

        def run(i):
            outs[i] = ar.allreduce(np.full(4, float(i + 1), np.float32))

        for round_idx in range(2):
            ts = [threading.Thread(target=run, args=(i,)) for i in range(3)]
            [t.start() for t in ts]
            [t.join() for t in ts]
            # thread sum = 1+2+3 = 6, cross multiplies by 10
            for i in range(3):
                np.testing.assert_allclose(outs[i], 60.0)
        assert len(calls) == 2  # exactly once per round
        np.testing.assert_allclose(calls[0], 6.0)

    def test_cross_reduce_failure_releases_waiters_and_recovers(self):
        """A raising cross_reduce must not strand waiters or wedge later
        rounds: every participant of the failed round raises, the next
        round works."""
        from multiverso_tpu.parallel.allreduce import RendezvousAllreduce
        boom = {"on": True}

        def cross(buf):
            if boom["on"]:
                raise ConnectionError("peer died")
            return buf

        ar = RendezvousAllreduce(2, cross_reduce=cross)
        errors = []
        outs = {}

        def run(i):
            try:
                outs[i] = ar.allreduce(np.full(2, float(i + 1), np.float32))
            except RuntimeError as e:
                errors.append(e)

        ts = [threading.Thread(target=run, args=(i,)) for i in range(2)]
        [t.start() for t in ts]
        [t.join(timeout=10) for t in ts]
        assert not any(t.is_alive() for t in ts), "waiters stranded"
        assert len(errors) == 2
        boom["on"] = False
        ts = [threading.Thread(target=run, args=(i,)) for i in range(2)]
        [t.start() for t in ts]
        [t.join(timeout=10) for t in ts]
        np.testing.assert_allclose(outs[0], 3.0)
        np.testing.assert_allclose(outs[1], 3.0)


class TestTwoProcessDevicePairs:
    """-device_pairs 1 across two processes (round 4): each process's
    padded token shard becomes one shard of a global batch-sharded
    vector; the fused program's gradients sum across processes inside
    the trace. Lockstep blocks (equal shard sizes here); both processes
    must save IDENTICAL embeddings (the PS state is one SPMD array)."""

    def test_we_device_pairs_across_two_processes(self, tmp_path):
        # topics 0-1 appear ONLY in shard 0, topics 2-3 only in shard 1:
        # topic structure for ALL FOUR topics in the saved vectors proves
        # both processes' gradients landed in the one PS state
        words = [f"w{i}" for i in range(20)]

        def gen(path, seed, sents, topics):
            r = np.random.default_rng(seed)
            with open(path, "w") as f:
                for _ in range(sents):
                    t = topics[r.integers(len(topics))]
                    f.write(" ".join(f"w{t * 5 + r.integers(5)}"
                                     for _ in range(10)) + "\n")

        # UNEQUAL shard sizes: rank 0 has more blocks than rank 1, so the
        # ragged-block protocol (finished ranks keep joining collectives
        # with empty filler blocks) is what keeps this from deadlocking
        gen(tmp_path / "corpus_0.txt", 5, 400, [0, 1])   # 2 blocks/epoch
        gen(tmp_path / "corpus_1.txt", 6, 150, [2, 3])   # 1 block/epoch
        with open(tmp_path / "vocab.txt", "w") as f:
            for w in words:
                f.write(f"{w} 100\n")
        run_two_process(_WE_CHILD, tmp_path, tmp_path, "pairs",
                        expect="WE OK")
        v0 = (tmp_path / "vectors_0.txt").read_text()
        v1 = (tmp_path / "vectors_1.txt").read_text()
        assert v0 == v1, "processes saved different embeddings"
        vecs = {l.split()[0]: np.array(l.split()[1:], float)
                for l in v0.splitlines()[1:]}

        def cos(a, b):
            return a @ b / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-9)

        for t in range(4):      # incl. topics only the OTHER shard saw
            same = np.mean([cos(vecs[f"w{5*t}"], vecs[f"w{5*t + k}"])
                            for k in range(1, 5)])
            cross = cos(vecs[f"w{5*t}"], vecs[f"w{(5*t + 7) % 20}"])
            assert same > cross, f"topic {t} not learned: {same} {cross}"


_LR_DEVICE_CHILD = r'''
import os, sys
rank, port, workdir, sparse = (int(sys.argv[1]), sys.argv[2], sys.argv[3],
                               sys.argv[4] == "sparse")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.models.logreg.configure import Configure
from multiverso_tpu.models.logreg.logreg import LogReg

os.chdir(workdir)
mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2"])
cfg = Configure(input_size=16, output_size=1, objective_type="sigmoid",
                updater_type="sgd", learning_rate=0.3, train_epoch=3,
                minibatch_size=32, use_ps=True, sync_frequency=2,
                sparse=sparse, device_plane=True, pipeline=False,
                train_file=f"train_{rank}.data", test_file="test.data",
                output_model_file="", output_file="",
                show_time_per_sample=10**9)
lr = LogReg(cfg)
lr.Train()
acc = lr.Test()
np.save(f"W_{rank}.npy", lr.model.weights())
mv.MV_Barrier()
mv.MV_ShutDown()
assert acc > 0.85, acc
print(f"child {rank} LRDEV acc {acc:.3f} OK", flush=True)
'''


class TestTwoProcessLogRegDevicePlane:
    """The LR device plane across two processes (round 4): per-process
    window tensors shard one global scan axis (dense) or ride the
    collective *_parts row round (sparse); summed lr-scaled deltas ARE
    the merged collective Add. Unequal shard sizes exercise the ragged
    filler-window protocol. Both ranks must end with IDENTICAL weights."""

    @pytest.mark.parametrize("mode", ["dense", "sparse"])
    def test_lr_device_plane_two_processes(self, tmp_path, mode):
        rng = np.random.default_rng(0)
        w_true = rng.normal(size=16)

        def write(path, n, seed):
            r = np.random.default_rng(seed)
            X = r.normal(size=(n, 16)).astype(np.float32)
            y = (X @ w_true > 0).astype(int)
            with open(path, "w") as f:
                for row, lab in zip(X, y):
                    if mode == "sparse":
                        nz = np.nonzero(row)[0]
                        f.write(f"{lab} " + " ".join(
                            f"{k}:{row[k]:.5f}" for k in nz) + "\n")
                    else:
                        f.write(f"{lab} " + " ".join(
                            f"{v:.5f}" for v in row) + "\n")

        write(tmp_path / "train_0.data", 640, 1)
        write(tmp_path / "train_1.data", 256, 2)   # RAGGED: fewer windows
        write(tmp_path / "test.data", 400, 3)
        run_two_process(_LR_DEVICE_CHILD, tmp_path, tmp_path, mode,
                        expect="LRDEV acc")
        W0 = np.load(tmp_path / "W_0.npy")
        W1 = np.load(tmp_path / "W_1.npy")
        np.testing.assert_array_equal(W0, W1)


class TestPjrtHeartbeatPlumbing:
    """MV_Init hands -mv_pjrt_heartbeat_s to the coordination service so
    long-lived shrunk worlds outlive the runtime's 100 s corpse
    detection. The value must REACH ``jax.distributed.initialize`` on
    the installed jax (a live multi-host init is environment-bound, so
    the public entry point is intercepted)."""

    def _set(self, name, value):
        import multiverso_tpu.zoo  # noqa: F401 — defines both flags
        from multiverso_tpu.utils.configure import SetCMDFlag
        SetCMDFlag(name, value)

    def _initialize_kwargs(self):
        """The kwargs one ``_dist_initialize`` hands the public API —
        bound against its real signature, so a renamed knob fails here
        instead of being dropped."""
        import inspect
        from unittest import mock

        import jax

        from multiverso_tpu.parallel import multihost as mh
        sig = inspect.signature(jax.distributed.initialize)
        seen = {}

        def fake(*args, **kw):
            seen.update(sig.bind(*args, **kw).arguments)

        with mock.patch.object(jax.distributed, "initialize", fake):
            mh._dist_initialize(coordinator_address="127.0.0.1:1",
                                num_processes=2, process_id=0)
        return seen

    def test_flag_reaches_initialize(self):
        self._set("mv_pjrt_heartbeat_s", 300)
        try:
            kw = self._initialize_kwargs()
        finally:
            self._set("mv_pjrt_heartbeat_s", 0)
        assert kw["heartbeat_timeout_seconds"] == 300
        assert kw["num_processes"] == 2

    def test_zero_means_runtime_default_unless_elastic(self):
        assert "heartbeat_timeout_seconds" not in \
            self._initialize_kwargs()
        self._set("mv_elastic", True)
        try:
            kw = self._initialize_kwargs()
        finally:
            self._set("mv_elastic", False)
        # elastic worlds default to a 600s budget
        assert kw["heartbeat_timeout_seconds"] == 600
