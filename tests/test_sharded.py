"""Sharded engine (round 12; sync/server.py ShardedServer).

The engine splits into per-table-group shard actors — each with its
own window stream, exchange stage and SEQ counter — routed by
``table_id % shards``; non-verb messages become CROSS-STREAM CUTS
(every shard fences at one agreed position, the payload runs once).
This file drives:

* single-process parity — the sharded engine's final table state is
  BIT-exact vs the ``-mv_engine_shards=1`` engine on an interleaved
  multi-table workload;
* cross-stream cut consistency — snapshot publish AND checkpoint save
  mid-fire-and-forget-burst capture every admitted Add on every shard
  and none after, and the two cut mechanisms agree bit-exactly;
* ops surfaces — /healthz reports a dead shard distinctly, the
  dashboard renders the [Engine] per-shard line;
* the 2-proc drills — sharded-vs-serial bit-exact parity over the shm
  wire's per-shard channels, and a chaos soak with
  ``-mv_engine_shards=2`` including ``apply.delay`` on ONE rank
  (a straggling shard must slow, never diverge).
"""

import numpy as np
import pytest

from tests.test_multihost import run_two_process


def _snap(name):
    from multiverso_tpu.telemetry import metrics
    return metrics.snapshot().get(name, {}).get("value", 0)


def _multi_table_workload(mv, tables, rng, rounds=12):
    """Interleaved tracked + fire-and-forget traffic across tables."""
    R = 64
    for i in range(rounds):
        for t in tables:
            ids = np.sort(rng.choice(R, 6, replace=False)).astype(
                np.int32)
            deltas = rng.integers(-3, 4, (6, 4)).astype(np.float32)
            if i % 3 == 0:
                t.AddRows(ids, deltas)
            else:
                t.AddFireForget(deltas, row_ids=ids)
    return [t.GetRows(np.arange(R, dtype=np.int32)) for t in tables]


class TestShardedSingleProcess:
    def test_auto_default_builds_sharded_engine(self):
        import multiverso_tpu as mv
        from multiverso_tpu.sync.server import ShardedServer
        from multiverso_tpu.tables import MatrixTableOption
        from multiverso_tpu.zoo import Zoo
        import os

        mv.MV_Init([])
        try:
            eng = Zoo.Get().server_engine
            if (os.cpu_count() or 1) >= 8:
                assert isinstance(eng, ShardedServer)
                t0 = mv.MV_CreateTable(MatrixTableOption(num_rows=8,
                                                         num_cols=2))
                t1 = mv.MV_CreateTable(MatrixTableOption(num_rows=8,
                                                         num_cols=2))
                # lazy spawn: table 0 rides shard 0 (the router), the
                # second table spawned its own shard actor
                assert t0.table_id == 0 and t1.table_id == 1
                assert 1 in eng._subs
                states = eng.shard_states()
                assert [s["shard"] for s in states] == [0, 1]
        finally:
            mv.MV_ShutDown()

    def test_explicit_one_is_the_plain_engine(self):
        import multiverso_tpu as mv
        from multiverso_tpu.sync.server import Server, ShardedServer
        from multiverso_tpu.zoo import Zoo

        mv.MV_Init(["-mv_engine_shards=1"])
        try:
            eng = Zoo.Get().server_engine
            assert type(eng) is Server
            assert not isinstance(eng, ShardedServer)
        finally:
            mv.MV_ShutDown()

    def test_sharded_vs_serial_bit_exact_parity(self):
        import multiverso_tpu as mv
        from multiverso_tpu.tables import MatrixTableOption

        results = {}
        for shards in (1, 4):
            mv.MV_Init([f"-mv_engine_shards={shards}"])
            try:
                tables = [mv.MV_CreateTable(MatrixTableOption(
                    num_rows=64, num_cols=4)) for _ in range(4)]
                rng = np.random.default_rng(99)
                results[shards] = _multi_table_workload(mv, tables, rng)
            finally:
                mv.MV_ShutDown()
        for a, b in zip(results[1], results[4]):
            np.testing.assert_array_equal(a, b)     # BIT-exact

    def test_cross_stream_cut_publish_and_checkpoint_agree(self,
                                                           tmp_path):
        """Mid-burst cuts: every Add admitted before the cut is in (on
        EVERY shard), none after, and the checkpoint cut bit-matches
        the publish cut taken back-to-back."""
        import multiverso_tpu as mv
        from multiverso_tpu.tables import MatrixTableOption

        mv.MV_Init(["-mv_engine_shards=3"])
        try:
            tables = [mv.MV_CreateTable(MatrixTableOption(
                num_rows=32, num_cols=4)) for _ in range(3)]
            rng = np.random.default_rng(5)
            pre = []
            for t in tables:
                ids = np.arange(8, dtype=np.int32)
                deltas = rng.integers(-3, 4, (8, 4)).astype(np.float32)
                for _ in range(6):          # fire-and-forget burst
                    t.AddFireForget(deltas, row_ids=ids)
                pre.append((ids, deltas))
            ckpt = str(tmp_path / "cut.bin")
            version = mv.MV_PublishSnapshot()   # cross-stream cut 1
            mv.MV_SaveCheckpoint(ckpt)          # cross-stream cut 2
            # post-cut traffic must not leak into the pinned version
            mv.MV_PinVersion(version)
            for t in tables:
                t.AddFireForget(np.full((8, 4), 100, np.float32),
                                row_ids=np.arange(8, dtype=np.int32))
            for tid, (ids, deltas) in enumerate(pre):
                served = mv.MV_ServingLookup(tid, ids, version=version)
                np.testing.assert_array_equal(served, deltas * 6)
            # the checkpoint cut (taken back-to-back, burst drained by
            # the publish fence) restores bit-identical to the version
            mv.MV_UnpinVersion(version)
        finally:
            mv.MV_ShutDown()
        mv.MV_Init(["-mv_engine_shards=3"])
        try:
            tables = [mv.MV_CreateTable(MatrixTableOption(
                num_rows=32, num_cols=4)) for _ in range(3)]
            mv.MV_LoadCheckpoint(ckpt)
            rng = np.random.default_rng(5)
            for tid, t in enumerate(tables):
                ids = np.arange(8, dtype=np.int32)
                deltas = rng.integers(-3, 4, (8, 4)).astype(np.float32)
                np.testing.assert_array_equal(t.GetRows(ids), deltas * 6)
        finally:
            mv.MV_ShutDown()

    def test_drain_and_finish_train_fence_every_shard(self):
        import multiverso_tpu as mv
        from multiverso_tpu.tables import MatrixTableOption
        from multiverso_tpu.zoo import Zoo

        mv.MV_Init(["-mv_engine_shards=2"])
        try:
            ts = [mv.MV_CreateTable(MatrixTableOption(num_rows=16,
                                                      num_cols=2))
                  for _ in range(2)]
            for t in ts:
                for _ in range(5):
                    t.AddFireForget(np.ones((4, 2), np.float32),
                                    row_ids=np.arange(4,
                                                      dtype=np.int32))
            zoo = Zoo.Get()
            c0 = zoo.server_engine.cut_count
            zoo.DrainServer()       # barrier ping = cross-stream cut
            assert zoo.server_engine.cut_count == c0 + 1
            for t in ts:            # every shard drained: all applied
                np.testing.assert_array_equal(
                    t.GetRows(np.arange(4, dtype=np.int32)),
                    np.full((4, 2), 5.0, np.float32))
        finally:
            mv.MV_ShutDown()


class TestShardedOpsSurfaces:
    def test_healthz_reports_dead_shard_distinctly(self):
        import multiverso_tpu as mv
        from multiverso_tpu.message import Message, MsgType
        from multiverso_tpu.tables import MatrixTableOption
        from multiverso_tpu.telemetry.ops import health_report
        from multiverso_tpu.zoo import Zoo
        import time

        mv.MV_Init(["-mv_engine_shards=2"])
        try:
            for _ in range(2):
                mv.MV_CreateTable(MatrixTableOption(num_rows=8,
                                                    num_cols=2))
            eng = Zoo.Get().server_engine
            rep = health_report()
            assert rep["healthy"] is True
            shards = rep["engine"]["shards"]
            assert [s["shard"] for s in shards] == [0, 1]
            assert rep["engine"]["transport"] == "local"
            # kill shard 1's loop thread through the real actor-death
            # path (a fence whose hold escapes with a BaseException)
            sub = eng._subs[1]

            class _Bomb:
                def hold(self):
                    raise SystemExit(7)

            sub.Receive(Message(msg_type=MsgType.Request_StoreLoad,
                                payload={"_mv_fence": _Bomb()}))
            t0 = time.monotonic()
            while sub._poison is None and time.monotonic() - t0 < 10:
                time.sleep(0.05)
            assert sub._poison is not None
            rep = health_report()
            assert rep["healthy"] is False
            assert any("shard 1 poisoned" in r for r in rep["reasons"])
        finally:
            mv.MV_ShutDown()

    def test_dashboard_engine_line(self):
        import multiverso_tpu as mv
        from multiverso_tpu.tables import MatrixTableOption
        from multiverso_tpu.utils.dashboard import Dashboard

        mv.MV_Init(["-mv_engine_shards=2"])
        try:
            for _ in range(2):
                mv.MV_CreateTable(MatrixTableOption(num_rows=8,
                                                    num_cols=2))
            out = Dashboard.DisplayAll()
            assert "[Engine] shards = 2" in out
            assert "transport = local" in out
            assert "s0:" in out and "s1:" in out
        finally:
            mv.MV_ShutDown()


_PARITY_CHILD = r'''
import os, sys
rank, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.tables import KVTableOption
from multiverso_tpu.parallel import multihost
from multiverso_tpu.zoo import Zoo

# two KV tables, one a shard stream (table_id % shards): a KV table's
# apply is host-local, which is what N streams in a multi-process world
# need (a MatrixTable's is a device collective and is refused there:
# TestShardedTwoProc.test_matrix_table_over_two_streams_is_refused)
R, K, ROUNDS = 200, 20, 10

def world(shards, coord_port):
    mv.MV_Init([f"-dist_coordinator=127.0.0.1:{coord_port}",
                f"-dist_rank={rank}", "-dist_size=2",
                f"-mv_engine_shards={shards}", "-mv_deadline_s=60"])
    eng = Zoo.Get().server_engine
    if shards > 1:
        assert type(eng).__name__ == "ShardedServer", type(eng)
        assert multihost.wire_name() == "shm", multihost.wire_name()
    wide = mv.MV_CreateTable(KVTableOption())
    kv = mv.MV_CreateTable(KVTableOption())
    rng = np.random.default_rng(31 + rank)
    for i in range(ROUNDS):
        ids = np.sort(rng.choice(R, K, replace=False)).astype(np.int64)
        # integer-valued deltas: float32 sums of small integers are
        # exact under ANY grouping, so "bit-exact" tests the PROTOCOL
        # (no verb lost/duplicated/misrouted), not summation order —
        # window boundaries legitimately differ between 1 and N shards
        deltas = rng.integers(-4, 5, K).astype(np.float32)
        wide.AddFireForget(ids, deltas)
        kv.AddFireForget(np.array([i, 900 + rank], np.int64),
                         np.ones(2, np.float32))
    if shards > 1:
        # a cross-stream cut mid-stream, on BOTH ranks (lockstep)
        v = mv.MV_PublishSnapshot()
    final = wide.Get(np.arange(R, dtype=np.int64))
    keys = np.array(sorted(set(list(range(ROUNDS)) + [900, 901])),
                    np.int64)
    kvv = kv.Get(keys)
    if shards > 1:
        subs = getattr(eng, "_subs", {})
        assert subs, "no sub-shards spawned"
        assert any(s.mh_window_exchanges > 0 for s in subs.values()), \
            "sub-shard stream never exchanged"
    mv.MV_Barrier()
    mv.MV_ShutDown()
    return final, kvv

f2, k2 = world(2, port)
# second world in the same processes: fresh coordinator port = port+1
f1, k1 = world(1, int(port) + 1)
np.testing.assert_array_equal(f1, f2)
np.testing.assert_array_equal(k1, k2)
print(f"child {rank} SHARD-PARITY OK", flush=True)
'''


_SHARD_CHAOS_CHILD = r'''
import os, sys
rank, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.tables import KVTableOption
from multiverso_tpu.zoo import Zoo

# full chaos on BOTH ranks (same seed: lockstep schedules) + an
# apply.delay PERF fault on rank 0 ONLY — one rank's shard applies
# straggle, which must slow the world, never diverge it
SPEC = "mailbox.dup:0.1,mailbox.delay:0.1@0.002,verb.transient:0.08"
if rank == 0:
    SPEC += ",apply.delay:0.5@0.01"
mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2", "-mv_engine_shards=2", "-mv_deadline_s=90",
            "-mv_max_retries=10",
            f"-chaos_spec={SPEC}", "-chaos_seed=4242"])
eng = Zoo.Get().server_engine
assert type(eng).__name__ == "ShardedServer", type(eng)
# KV tables: their applies are host-local, as two streams in a
# multi-process world need (see _PARITY_CHILD)
R = 48
t0 = mv.MV_CreateTable(KVTableOption())
t1 = mv.MV_CreateTable(KVTableOption())
rng = np.random.default_rng(77 + rank)
for i in range(14):
    for t in (t0, t1):
        ids = np.sort(rng.choice(R, 5, replace=False)).astype(np.int64)
        deltas = rng.integers(-4, 5, 5).astype(np.float32)
        if i % 4 == 0:
            t.Add(ids, deltas)
        else:
            t.AddFireForget(ids, deltas)
from multiverso_tpu.failsafe import chaos
chaos.quiesce()
mv.MV_SetFlag("chaos_spec", "")
chaos.quiesce()
got0 = t0.Get(np.arange(R, dtype=np.int64))
got1 = t1.Get(np.arange(R, dtype=np.int64))
oracle0 = np.zeros(R, np.float32)
oracle1 = np.zeros(R, np.float32)
for r in range(2):
    orng = np.random.default_rng(77 + r)
    for i in range(14):
        for oracle in (oracle0, oracle1):
            ids = np.sort(orng.choice(R, 5, replace=False))
            deltas = orng.integers(-4, 5, 5).astype(np.float32)
            np.add.at(oracle, ids, deltas)
np.testing.assert_array_equal(got0, oracle0)
np.testing.assert_array_equal(got1, oracle1)
from multiverso_tpu.telemetry import metrics as tmetrics
if rank == 0:
    assert tmetrics.snapshot().get("chaos.apply.delay",
                                   {}).get("value", 0) > 0, \
        "the apply.delay fault never engaged on the delayed rank"
mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} SHARD-CHAOS OK", flush=True)
'''


_REFUSED_CHILD = r'''
import os, sys
rank, port, kind = int(sys.argv[1]), sys.argv[2], sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu import tables

mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2", "-mv_engine_shards=2", "-mv_deadline_s=60"])
option = getattr(tables, kind)
t0 = mv.MV_CreateTable(option(num_rows=16, num_cols=4))
t1 = mv.MV_CreateTable(option(num_rows=16, num_cols=4))
try:
    t1.AddRows(np.array([1, 2], np.int32), np.ones((2, 4), np.float32))
    print(f"child {rank} NOT REFUSED", flush=True)
except Exception as e:
    print(f"child {rank} RAISED {type(e).__name__}: {e}", flush=True)
os._exit(0)     # the engine is dead: no shutdown handshake to wait for
'''


class TestShardedTwoProc:
    @pytest.mark.parametrize("kind", ["MatrixTableOption",
                                      "SparseMatrixTableOption"])
    def test_matrix_table_over_two_streams_is_refused(self, tmp_path, kind):
        """A table whose apply is a device collective cannot ride N
        shard streams in a multi-process world: the first window says so
        at the caller's Wait, on every rank, on the CPU backend as on the
        chip (sync/server.py ``_mh_fence_cause``)."""
        outs = run_two_process(_REFUSED_CHILD, tmp_path, kind,
                               expect="RAISED FatalError")
        for out in outs:
            assert "NOT REFUSED" not in out
            assert ("window requires a collective apply "
                    "(nonlocal_table)") in out
            assert "shard streams in a multi-process world" in out
            assert "run -mv_engine_shards=1" in out

    def test_sharded_vs_serial_bit_exact_parity_2proc(self, tmp_path):
        run_two_process(_PARITY_CHILD, tmp_path,
                        expect="SHARD-PARITY OK")

    def test_chaos_soak_with_delayed_shard_converges(self, tmp_path):
        run_two_process(_SHARD_CHAOS_CHILD, tmp_path,
                        expect="SHARD-CHAOS OK")
