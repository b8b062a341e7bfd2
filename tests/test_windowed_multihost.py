"""Windowed multi-process engine protocol (round 5; sync/server.py).

The r4 engine took the strict path for any ``nproc > 1`` world: every
table verb ran its own host collective (~2 allgather rounds per verb)
and every single-process window optimization (add-coalescing, get-dedup,
merged runs) was disabled. The windowed protocol
exchanges a whole engine window in ONE allgather and re-enables all of
them across ranks. These tests drive the new surface with 2-process
jax.distributed worlds (tests/test_multihost.py run_two_process
pattern):

* burst coalescing — fire-and-forget Add bursts from both ranks merge
  into few dispatches; the result matches the sequential oracle;
* the collective-count contract itself — host collective rounds per
  verb must sit far below the r4 cost of ~2/verb (the round-5 VERDICT
  metric);
* compressed wire across processes — a 2-proc sparse-compressed Add
  stream applies bit-identically to an uncompressed twin (VERDICT #3);
* deterministic failure — an invalid payload at one rank fails that
  collective position on BOTH ranks (the r4 design would deadlock: the
  bad rank replied early while the good rank entered the merge
  allgather alone).
"""

import numpy as np
import pytest

from tests.test_multihost import run_two_process

_BURST_CHILD = r'''
import os, sys
rank, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.tables import ArrayTableOption, MatrixTableOption
from multiverso_tpu.parallel import multihost
from multiverso_tpu.zoo import Zoo

mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2"])
R, C, K, ROUNDS = 500, 8, 40, 12
mat = mv.MV_CreateTable(MatrixTableOption(num_rows=R, num_cols=C))
arr = mv.MV_CreateTable(ArrayTableOption(size=32))

rng = np.random.default_rng(7 + rank)
ids_pool = [np.sort(rng.choice(R, K, replace=False)).astype(np.int32)
            for _ in range(ROUNDS)]
deltas_pool = [rng.standard_normal((K, C)).astype(np.float32)
               for _ in range(ROUNDS)]

# warm one verb of each kind, then count collectives over the burst
mat.AddRows(ids_pool[0], deltas_pool[0])
mat.GetRows(ids_pool[0])
arr.Add(np.ones(32, np.float32))
arr.Get()
base = dict(multihost.STATS)
verbs = 0
# burst: interleaved fire-and-forget adds + async gets on two tables —
# the engine windows coalesce them; strict r4 would pay ~2 collectives
# per verb
handles = []
for i in range(1, ROUNDS):
    mat.AddFireForget(deltas_pool[i], row_ids=ids_pool[i])
    arr.AddFireForget(np.full(32, 0.5, np.float32))
    handles.append(mat.GetAsyncHandle(row_ids=ids_pool[i]))
    verbs += 3
for h in handles:
    mat.Wait(h)
final_rows = mat.GetRows(np.arange(R, dtype=np.int32)); verbs += 1
final_arr = arr.Get(); verbs += 1
used = multihost.STATS["host_collective_rounds"] - base["host_collective_rounds"]
per_verb = used / verbs
# r4 strict cost ~2/verb; the windowed protocol must be at least 4x off
assert per_verb < 0.5, (used, verbs, per_verb)

# oracle: both ranks' adds all land (sum over ranks and rounds)
oracle = np.zeros((R, C), np.float32)
for r in range(2):
    orng = np.random.default_rng(7 + r)
    oids = [np.sort(orng.choice(R, K, replace=False)).astype(np.int32)
            for _ in range(ROUNDS)]
    odeltas = [orng.standard_normal((K, C)).astype(np.float32)
               for _ in range(ROUNDS)]
    for i in range(ROUNDS):
        np.add.at(oracle, oids[i], odeltas[i])
np.testing.assert_allclose(final_rows, oracle, rtol=1e-4, atol=1e-4)
assert np.allclose(final_arr, 1.0 * 2 + 0.5 * 2 * (ROUNDS - 1))

# the engine actually windowed: exchanges < verbs processed
srv = Zoo.Get().server_engine
assert srv.mh_window_verbs >= verbs, (srv.mh_window_verbs, verbs)
assert srv.mh_window_exchanges < srv.mh_window_verbs, (
    srv.mh_window_exchanges, srv.mh_window_verbs)
mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} BURST OK per_verb={per_verb:.3f}", flush=True)
'''


_COMPRESS_CHILD = r'''
import os, sys
rank, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.tables import MatrixTableOption

mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2"])
R, C = 128, 16
comp = mv.MV_CreateTable(MatrixTableOption(num_rows=R, num_cols=C,
                                           compress="sparse"))
plain = mv.MV_CreateTable(MatrixTableOption(num_rows=R, num_cols=C))
rng = np.random.default_rng(3 + rank)
for step in range(6):
    ids = np.sort(rng.choice(R, 12, replace=False)).astype(np.int32)
    deltas = np.zeros((12, C), np.float32)
    # >50% zeros on even steps (compresses); dense on odd (per-rank
    # dense fallback mixes with the peer's compressed payload)
    nz = 3 if step % 2 == 0 else C
    deltas[:, :nz] = rng.standard_normal((12, nz)).astype(np.float32)
    comp.AddRows(ids, deltas)
    plain.AddRows(ids, deltas)
got_c = comp.GetRows(np.arange(R, dtype=np.int32))
got_p = plain.GetRows(np.arange(R, dtype=np.int32))
# sparse compression is EXACT: bit-identical to the uncompressed twin
np.testing.assert_array_equal(got_c, got_p)
# the compressed wire actually engaged (even steps compressed)
ws = comp.server().wire_stats
assert ws["dense_bytes"] > 0 and ws["payload_bytes"] > 0, ws
assert ws["payload_bytes"] < ws["dense_bytes"], ws

# 1bit across processes: LOSSY (sign bits + row means, per-rank error
# feedback) — repeated constant per-rank deltas to disjoint rows must
# track the uncompressed twin closely (feedback cancels the rounding)
one = mv.MV_CreateTable(MatrixTableOption(num_rows=R, num_cols=C,
                                          compress="1bit"))
ptwin = mv.MV_CreateTable(MatrixTableOption(num_rows=R, num_cols=C))
my_rows = np.arange(8, dtype=np.int32) + rank * 16
const = np.tile(np.linspace(-1.0, 1.0, C, dtype=np.float32), (8, 1))
for _ in range(8):
    one.AddRows(my_rows, const)
    ptwin.AddRows(my_rows, const)
both = np.concatenate([np.arange(8), np.arange(8) + 16]).astype(np.int32)
a = one.GetRows(both)     # OWN rows AND the peer's: cross-rank 1bit
b = ptwin.GetRows(both)   # delivery must decode correctly too
assert np.abs(b).max() > 0, "twin rows empty — adds never landed"
assert np.abs(a - b).max() < 0.35 * np.abs(b).max(), (
    np.abs(a - b).max(), np.abs(b).max())
ws1 = one.server().wire_stats
assert ws1["payload_bytes"] < ws1["dense_bytes"], ws1
mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} COMPRESS OK", flush=True)
'''


_BADADD_CHILD = r'''
import os, sys
rank, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.tables import MatrixTableOption

mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2"])
mat = mv.MV_CreateTable(MatrixTableOption(num_rows=16, num_cols=2))
# rank 1 pushes an OUT-OF-RANGE row id at the same collective position
# as rank 0's valid add: the position must fail DETERMINISTICALLY on
# both ranks (r4's design deadlocked here — the bad rank replied before
# its collective, stranding the good rank in the allgather)
ids = np.array([1, 99 if rank == 1 else 2], np.int32)
try:
    mat.AddRows(ids, np.ones((2, 2), np.float32))
    failed = False
except Exception:
    failed = True
assert failed, "invalid collective add did not raise"
# the world is still alive and consistent afterwards
mat.AddRows(np.array([3], np.int32), np.ones((1, 2), np.float32))
rows = mat.GetRows(np.array([1, 2, 3], np.int32))
assert np.allclose(rows[0], 0.0) and np.allclose(rows[2], 2.0), rows
mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} BADADD OK", flush=True)
'''


_CKPT_BURST_CHILD = r'''
import os, sys
rank, port, ckpt = int(sys.argv[1]), sys.argv[2], sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.tables import MatrixTableOption

mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2"])
mat = mv.MV_CreateTable(MatrixTableOption(num_rows=32, num_cols=4))
ids = np.array([rank, 10 + rank], np.int32)
# burst of fire-and-forget adds, then a checkpoint save: the StoreLoad
# message BARRIERS the collective window at a lockstep position (its
# fetch is itself collective), so the snapshot must contain exactly the
# adds acknowledged-or-enqueued before it on BOTH ranks
for _ in range(5):
    mat.AddFireForget(np.ones((2, 4), np.float32), row_ids=ids)
mv.MV_SaveCheckpoint(ckpt)
# more adds AFTER the snapshot, then restore: they must be wiped
for _ in range(3):
    mat.AddFireForget(np.ones((2, 4), np.float32), row_ids=ids)
mv.MV_LoadCheckpoint(ckpt)
rows = mat.GetRows(np.array([0, 1, 10, 11], np.int32))
assert np.allclose(rows[[0, 2]], 5.0), rows   # rank 0's burst only
assert np.allclose(rows[[1, 3]], 5.0), rows   # rank 1's burst only
mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} CKPT BURST OK", flush=True)
'''


_DIVERGE_CHILD = r'''
import os, sys
rank, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.tables import ArrayTableOption, MatrixTableOption

mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2"])
arr = mv.MV_CreateTable(ArrayTableOption(size=8))
mat = mv.MV_CreateTable(MatrixTableOption(num_rows=8, num_cols=2))
# CONTRACT VIOLATION: rank 0 Adds to table 0 while rank 1 Adds to table
# 1 at the same global position — the windowed engine must detect the
# divergent descriptors and raise on BOTH ranks (not corrupt, not hang;
# the r4 strict path would have silently merged mismatched tables)
try:
    if rank == 0:
        arr.Add(np.ones(8, np.float32))
    else:
        mat.AddRows(np.array([1], np.int32), np.ones((1, 2), np.float32))
    print(f"child {rank} NO ERROR", flush=True)
except Exception as e:
    print(f"child {rank} DIVERGE RAISED {type(e).__name__}", flush=True)
os._exit(0)
'''


class TestWindowedProtocol:
    def test_divergent_verb_streams_raise_on_every_rank(self, tmp_path):
        """Mismatched verb sequences across ranks are a contract
        violation: the windowed engine's prefix CHECK must raise loudly
        on BOTH ranks instead of corrupting state or hanging."""
        outs = run_two_process(_DIVERGE_CHILD, tmp_path,
                               expect="DIVERGE RAISED")
        for out in outs:
            assert "NO ERROR" not in out

    def test_burst_coalescing_and_collective_budget(self, tmp_path):
        """Interleaved 2-rank bursts: result equals the oracle AND the
        host-collective cost per verb sits far below r4's ~2/verb."""
        run_two_process(_BURST_CHILD, tmp_path, expect="BURST OK",
                        timeout=280)

    def test_compressed_wire_across_processes(self, tmp_path):
        """compress='sparse' Adds from two ranks (mixed with per-rank
        dense fallbacks) apply bit-identically to an uncompressed twin
        (VERDICT #3: the bandwidth saver now works exactly where bytes
        cross nodes)."""
        run_two_process(_COMPRESS_CHILD, tmp_path, expect="COMPRESS OK")

    def test_checkpoint_barriers_windows_across_ranks(self, tmp_path):
        """A StoreLoad inside a 2-proc fire-and-forget burst barriers the
        collective window at a lockstep position: the snapshot holds
        exactly the pre-barrier adds, and post-snapshot adds restore
        away cleanly on both ranks."""
        run_two_process(_CKPT_BURST_CHILD, tmp_path,
                        f"file://{tmp_path}/ck.mvt", expect="CKPT BURST OK")

    def test_invalid_position_fails_on_both_ranks(self, tmp_path):
        """An invalid payload at one rank fails that collective position
        deterministically on BOTH ranks instead of deadlocking, and the
        world keeps working."""
        run_two_process(_BADADD_CHILD, tmp_path, expect="BADADD OK")


_ARRAY_BURST_CHILD = r'''
import os, sys
rank, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.tables import ArrayTableOption
from multiverso_tpu.zoo import Zoo

mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2"])
N, SZ = 16, 64
arr = mv.MV_CreateTable(ArrayTableOption(size=SZ))
arr.Add(np.ones(SZ, np.float32))                       # warm
srv = Zoo.Get().server_engine
d0, m0 = srv.mh_add_dispatches, srv.mh_add_run_merged
# fire-and-forget burst: N whole-table adds coalesce into merged
# dispatches (round 6 extended ProcessAddRunParts to ArrayTable — the
# engine applies a window's run as ONE pre-summed apply)
for i in range(N):
    arr.AddFireForget(np.full(SZ, 0.5, np.float32))
got = arr.Get()                                        # drains the burst
used = srv.mh_add_dispatches - d0
merged = srv.mh_add_run_merged - m0
# one merged dispatch per window the burst landed in — far fewer
# dispatches than the 2N cross-rank positions, and >=1 actually merged
assert merged >= 1, (used, merged)
assert used <= N // 2, (used, merged)
# oracle: warm (1.0 x 2 ranks) + burst (0.5 x N x 2 ranks)
assert np.allclose(got, 2.0 + 0.5 * N * 2), got[:4]
mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} ARRBURST OK dispatches={used} merged={merged}",
      flush=True)
'''


_KV_BURST_CHILD = r'''
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

mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2", "-mv_write_combine=0"])  # the ENGINE's merge
# machinery is under test: worker-side combining would collapse the
# burst before the window ever sees it
N = 16
kv = mv.MV_CreateTable(KVTableOption())
kv.Add(np.array([7], np.int64), np.array([1.0], np.float32))   # warm
srv = Zoo.Get().server_engine
d0, m0 = srv.mh_add_dispatches, srv.mh_add_run_merged
# divergent per-rank key sets incl. keys FIRST SEEN mid-burst: the
# merged scatter-add must preserve first-sight slot-creation order
for i in range(N):
    keys = np.array([(rank + 1) * 100 + i, 7, 50 + i], np.int64)
    kv.AddFireForget(keys, np.full(3, 1.0, np.float32))
got = kv.Get(np.array([7], np.int64))                  # drains the burst
used = srv.mh_add_dispatches - d0
merged = srv.mh_add_run_merged - m0
assert merged >= 1, (used, merged)
assert used <= N // 2, (used, merged)
# oracle: key 7 = warm (1 x 2 ranks) + burst (1 x N x 2 ranks)
assert np.allclose(got, 2.0 + N * 2), got
# per-rank keys and mid-burst keys all landed with consistent slots
mine = kv.Get(np.arange(N, dtype=np.int64) + (rank + 1) * 100)
peer = kv.Get(np.arange(N, dtype=np.int64) + (2 - rank) * 100)
assert np.allclose(mine, 1.0) and np.allclose(peer, 1.0), (mine, peer)
assert np.allclose(kv.Get(np.arange(N, dtype=np.int64) + 50), 2.0)
mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} KVBURST OK dispatches={used} merged={merged}",
      flush=True)
'''


_TRANSPORT_CHILD = r'''
import os, sys
rank, port, mode = int(sys.argv[1]), sys.argv[2], sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.tables import (ArrayTableOption, KVTableOption,
                                   MatrixTableOption)
from multiverso_tpu.zoo import Zoo

flags = [f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
         "-dist_size=2"]
if mode == "auto":
    # auto with a floor far below these payloads: eligible Add values
    # must ride the device wire (the pod-deployment configuration)
    flags += ["-window_transport=auto", "-window_device_min_bytes=1024"]
else:
    flags += ["-window_transport=host"]
mv.MV_Init(flags)
R, C, K = 256, 16, 32
mat = mv.MV_CreateTable(MatrixTableOption(num_rows=R, num_cols=C))
arr = mv.MV_CreateTable(ArrayTableOption(size=2048))
kv = mv.MV_CreateTable(KVTableOption())
srv = Zoo.Get().server_engine

rng = np.random.default_rng(11 + rank)
ids = np.sort(rng.choice(R, K, replace=False)).astype(np.int32)
deltas = rng.standard_normal((K, C)).astype(np.float32)   # 2KB > floor
mat.AddRows(ids, deltas)
arr.Add(np.full(2048, float(rank + 1), np.float32))       # 8KB > floor
kv.Add(np.array([3, 4], np.int64), np.ones(2, np.float32))  # never defers

dev = srv.mh_device_wire_adds
if mode == "auto":
    # matrix row-set + array whole-table rode the device wire; the KV
    # payload stayed on the host wire (keys must cross it anyway)
    assert dev == 2, dev
else:
    assert dev == 0, dev

# results identical either way: transport must not change semantics
oracle = np.zeros((R, C), np.float32)
for r in range(2):
    orng = np.random.default_rng(11 + r)
    oids = np.sort(orng.choice(R, K, replace=False)).astype(np.int32)
    od = orng.standard_normal((K, C)).astype(np.float32)
    np.add.at(oracle, oids, od)
np.testing.assert_allclose(mat.GetRows(np.arange(R, dtype=np.int32)),
                           oracle, rtol=1e-4, atol=1e-4)
assert np.allclose(arr.Get(), 3.0), arr.Get()[:4]
assert np.allclose(kv.Get(np.array([3, 4], np.int64)), 2.0)
mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} TRANSPORT OK dev={dev}", flush=True)
'''


_MIXED_RUN_CHILD = r'''
import os, sys
rank, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.tables import MatrixTableOption
from multiverso_tpu.zoo import Zoo

mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2", "-window_transport=auto",
            "-window_device_min_bytes=1024", "-mv_write_combine=0"])
# (combining off: per-POSITION transport selection is under test —
# worker-side concat would merge small host payloads into big deferred
# ones before the engine picks a wire)
R, C, ROUNDS, SMALL = 256, 16, 6, 6
mat = mv.MV_CreateTable(MatrixTableOption(num_rows=R, num_cols=C))
mat.AddRows(np.array([0], np.int32), np.zeros((1, C), np.float32))  # warm
srv = Zoo.Get().server_engine
d0, m0, v0 = (srv.mh_add_dispatches, srv.mh_add_run_merged,
              srv.mh_device_wire_adds)
rng = np.random.default_rng(5 + rank)
big_ids = [np.sort(rng.choice(R, 32, replace=False)).astype(np.int32)
           for _ in range(ROUNDS)]
big_deltas = [rng.standard_normal((32, C)).astype(np.float32)
              for _ in range(ROUNDS)]          # 2KB >= floor: defers
positions = 0
for i in range(ROUNDS):
    mat.AddFireForget(big_deltas[i], row_ids=big_ids[i])
    positions += 1
    for j in range(SMALL):
        # 64B < floor: stays on the host wire
        mat.AddFireForget(np.ones((1, C), np.float32),
                          row_ids=np.array([j], np.int32))
        positions += 1
got = mat.GetRows(np.arange(R, dtype=np.int32))     # drains the burst
used = srv.mh_add_dispatches - d0
merged = srv.mh_add_run_merged - m0
dev = srv.mh_device_wire_adds - v0
# the big Adds rode the device wire AND the small host-wire positions
# still applied as merged dispatches: one deferred position must not
# demote its run-mates to per-position applies
assert dev >= 1, (used, merged, dev)
assert merged >= 1, (used, merged, dev)
assert used <= positions // 2, (used, positions)
oracle = np.zeros((R, C), np.float32)
for r in range(2):
    orng = np.random.default_rng(5 + r)
    oids = [np.sort(orng.choice(R, 32, replace=False)).astype(np.int32)
            for _ in range(ROUNDS)]
    od = [orng.standard_normal((32, C)).astype(np.float32)
          for _ in range(ROUNDS)]
    for i in range(ROUNDS):
        np.add.at(oracle, oids[i], od[i])
oracle[:SMALL] += ROUNDS * 2.0          # small burst, both ranks
np.testing.assert_allclose(got, oracle, rtol=1e-4, atol=1e-4)
mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} MIXEDRUN OK used={used} merged={merged} dev={dev}",
      flush=True)
'''


_DEVICE_BURST_CHILD = r'''
import os, sys
rank, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.tables import ArrayTableOption, MatrixTableOption
from multiverso_tpu.zoo import Zoo

mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2", "-window_transport=auto",
            "-window_device_min_bytes=512", "-mv_write_combine=0"])
# (combining off: the per-position device-wire deferral + merged
# device rounds are under test)
R, C, N = 256, 16, 8
mat = mv.MV_CreateTable(MatrixTableOption(num_rows=R, num_cols=C))
arr = mv.MV_CreateTable(ArrayTableOption(size=512))
mat.AddRows(np.array([0], np.int32), np.zeros((1, C), np.float32))
arr.Add(np.zeros(512, np.float32))                    # warm both
srv = Zoo.Get().server_engine
d0, m0, v0 = (srv.mh_add_dispatches, srv.mh_add_run_merged,
              srv.mh_device_wire_adds)
rng = np.random.default_rng(9 + rank)
ids = [np.sort(rng.choice(R, 32, replace=False)).astype(np.int32)
       for _ in range(N)]
deltas = [rng.standard_normal((32, C)).astype(np.float32)
          for _ in range(N)]                          # 2KB each: defers
for i in range(N):
    mat.AddFireForget(deltas[i], row_ids=ids[i])
    arr.AddFireForget(np.full(512, 0.5, np.float32))  # 2KB: defers
got = mat.GetRows(np.arange(R, dtype=np.int32))       # drains the burst
got_arr = arr.Get()
used = srv.mh_add_dispatches - d0
merged = srv.mh_add_run_merged - m0
dev = srv.mh_device_wire_adds - v0
# EVERY burst Add rode the device wire, and deferred runs applied as
# merged device rounds (ProcessAddRunPartsDevice) — far fewer
# dispatches than the 2N positions per table
assert dev == 2 * N, (used, merged, dev)
assert merged >= 1, (used, merged, dev)
assert used <= N, (used, merged, dev)
oracle = np.zeros((R, C), np.float32)
for r in range(2):
    orng = np.random.default_rng(9 + r)
    oids = [np.sort(orng.choice(R, 32, replace=False)).astype(np.int32)
            for _ in range(N)]
    od = [orng.standard_normal((32, C)).astype(np.float32)
          for _ in range(N)]
    for i in range(N):
        np.add.at(oracle, oids[i], od[i])
np.testing.assert_allclose(got, oracle, rtol=1e-4, atol=1e-4)
assert np.allclose(got_arr, 0.5 * N * 2), got_arr[:4]
mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} DEVBURST OK used={used} merged={merged} dev={dev}",
      flush=True)
'''


class TestPerTableBurstsAndTransport:
    """Round 6: merged add-runs on every table family, and the adaptive
    window transport (parallel/wire.py codec + -window_transport)."""

    def test_array_burst_merges_dispatches(self, tmp_path):
        """A 2-proc ArrayTable fire-and-forget burst applies as merged
        dispatches (ProcessAddRunParts extended beyond MatrixTable):
        the engine's dispatch counters must show actual cross-position
        merging, and the summed result must match the oracle."""
        run_two_process(_ARRAY_BURST_CHILD, tmp_path, expect="ARRBURST OK")

    def test_kv_burst_merges_dispatches(self, tmp_path):
        """A 2-proc KVTable fire-and-forget burst (divergent key sets,
        keys first seen mid-burst) applies as merged scatter-adds with
        the slot index evolving identically on both ranks."""
        run_two_process(_KV_BURST_CHILD, tmp_path, expect="KVBURST OK")

    def test_device_burst_merges_device_runs(self, tmp_path):
        """A 2-proc burst whose Adds ALL ride the device wire applies
        as merged device rounds (ProcessAddRunPartsDevice on matrix +
        array tables): one collective parts program per run instead of
        one per position, with the summed result matching the oracle."""
        run_two_process(_DEVICE_BURST_CHILD, tmp_path, expect="DEVBURST OK",
                        timeout=280)

    def test_mixed_run_merges_host_subset(self, tmp_path):
        """A run mixing one device-wire (deferred) Add with a host-wire
        burst on the same table still applies the host positions as
        merged dispatches — a large deferred payload must not demote
        its run-mates to per-position applies."""
        run_two_process(_MIXED_RUN_CHILD, tmp_path, expect="MIXEDRUN OK",
                        timeout=280)

    @pytest.mark.parametrize("mode", ["auto", "host"])
    def test_transport_selection(self, tmp_path, mode):
        """-window_transport auto (with a low -window_device_min_bytes
        floor, the pod configuration) routes eligible Add values over
        the DEVICE wire — only dtype/shape metadata crosses the host
        exchange — while host mode keeps everything on the staging
        allgather; results are identical either way."""
        run_two_process(_TRANSPORT_CHILD, tmp_path, mode,
                        expect="TRANSPORT OK")


_THREE_CHILD = r'''
import os, sys
rank, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.tables import (ArrayTableOption, KVTableOption,
                                   MatrixTableOption)

mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=3"])
assert mv.MV_Size() == 3
arr = mv.MV_CreateTable(ArrayTableOption(size=12))
arr.Add(np.full(12, float(rank + 1), np.float32))
assert np.allclose(arr.Get(), 6.0)          # 1+2+3
mat = mv.MV_CreateTable(MatrixTableOption(num_rows=30, num_cols=4))
ids = np.array([rank, 10 + rank, 20], np.int32)   # 20 shared by ALL
mat.AddRows(ids, np.full((3, 4), float(rank + 1), np.float32))
rows = mat.GetRows(np.array([0, 1, 2, 10, 11, 12, 20], np.int32))
assert np.allclose(rows[:3], [[1] * 4, [2] * 4, [3] * 4]), rows
assert np.allclose(rows[6], 6.0), rows
kv = mv.MV_CreateTable(KVTableOption())
kv.Add(np.array([100 + rank, 999], np.int64), np.ones(2, np.float32))
assert np.allclose(kv.Get(np.array([100, 101, 102, 999], np.int64)),
                   [1, 1, 1, 3.0])
# fire-and-forget burst through the windowed engine, 3 ranks
hs = []
for _ in range(5):
    mat.AddFireForget(np.ones((3, 4), np.float32), row_ids=ids)
    hs.append(mat.GetAsyncHandle(row_ids=ids))
for h in hs:
    mat.Wait(h)
assert np.allclose(mat.GetRows(np.array([20], np.int32)),
                   6.0 + 3 * 5), "3-rank burst merge wrong"
mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} THREE OK", flush=True)
'''


class TestThreeProcessWorld:
    """Rank-count generality: nothing in the windowed protocol, the
    parts merges, or the mirrors is 2-specific — a 3-process world
    (divergent payloads, a row all ranks share, a coalesced burst)
    behaves per the same contracts."""

    def test_three_process_tables_and_burst(self, tmp_path):
        from tests.test_multihost import run_n_process
        run_n_process(_THREE_CHILD, tmp_path, nproc=3, expect="THREE OK")


_ORACLE_WALK_CHILD = r'''
import os, sys
rank, port, seed = int(sys.argv[1]), sys.argv[2], int(sys.argv[3])
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
R, C, A = 64, 3, 16
mat = mv.MV_CreateTable(MatrixTableOption(num_rows=R, num_cols=C))
arr = mv.MV_CreateTable(ArrayTableOption(size=A))
kv = mv.MV_CreateTable(KVTableOption())

# one SHARED program rng drives the verb sequence (identical on both
# ranks — the SPMD contract) and per-rank payload rngs drive the data.
# Verbs mix blocking and fire-and-forget so window boundaries race;
# the oracle accumulates both ranks' payload streams independently.
prog = np.random.default_rng(seed)
pay = [np.random.default_rng(1000 * seed + r) for r in range(2)]
o_mat = np.zeros((R, C), np.float32)
o_arr = np.zeros(A, np.float32)
o_kv = {}

for step in range(60):
    verb = prog.integers(6)
    datas = []
    for r in range(2):
        if verb == 0:      # matrix row add (maybe duplicate ids)
            n = int(pay[r].integers(1, 6))
            ids = pay[r].integers(0, R, n).astype(np.int32)
            d = pay[r].standard_normal((n, C)).astype(np.float32)
            datas.append((ids, d))
        elif verb == 1:    # matrix whole add
            datas.append(pay[r].standard_normal((R, C)).astype(np.float32))
        elif verb == 2:    # matrix row get
            n = int(pay[r].integers(1, 6))
            datas.append(np.unique(pay[r].integers(0, R, n)).astype(np.int32))
        elif verb == 3:    # array add
            datas.append(pay[r].standard_normal(A).astype(np.float32))
        elif verb == 4:    # kv add
            n = int(pay[r].integers(1, 5))
            keys = pay[r].integers(0, 40, n).astype(np.int64)
            vals = pay[r].standard_normal(n).astype(np.float32)
            datas.append((keys, vals))
        else:              # kv get
            datas.append(np.unique(pay[r].integers(0, 40,
                         int(pay[r].integers(1, 5)))).astype(np.int64))
    mine = datas[rank]
    if verb == 0:
        if prog.integers(2):
            mat.AddRows(*mine)
        else:
            mat.AddFireForget(mine[1], row_ids=mine[0])
        for ids, d in datas:
            np.add.at(o_mat, ids, d)
    elif verb == 1:
        mat.Add(mine)
        for d in datas:
            o_mat += d
    elif verb == 2:
        got = mat.GetRows(mine)
        assert got.shape == (len(mine), C)
    elif verb == 3:
        if prog.integers(2):
            arr.Add(mine)
        else:
            arr.AddFireForget(mine)
        for d in datas:
            o_arr += d
    elif verb == 4:
        kv.Add(*mine)
        for keys, vals in datas:
            for k, v in zip(keys.tolist(), vals.tolist()):
                o_kv[k] = o_kv.get(k, 0.0) + v
    else:
        got = kv.Get(mine)
        assert got.shape == mine.shape

# final state must equal the oracle exactly on BOTH ranks (linear f32
# sums are order-insensitive only up to rounding -> loose tolerance)
np.testing.assert_allclose(mat.GetRows(np.arange(R, dtype=np.int32)),
                           o_mat, rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(arr.Get(), o_arr, rtol=2e-4, atol=2e-4)
all_keys = np.array(sorted(o_kv), np.int64)
np.testing.assert_allclose(kv.Get(all_keys),
                           [o_kv[int(k)] for k in all_keys],
                           rtol=2e-4, atol=2e-4)
mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} WALK OK", flush=True)
'''


class TestWindowedOracleWalk:
    """Randomized 2-proc verb walks (mixed tables, blocking and
    fire-and-forget, whole-table and row/key payloads, within-batch
    duplicates) against a host oracle: whatever window boundaries the
    engines race into, the merged state must equal the sum of both
    ranks' payload streams."""

    @pytest.mark.parametrize("seed", [11, 23])
    def test_randomized_walk_matches_oracle(self, tmp_path, seed):
        run_two_process(_ORACLE_WALK_CHILD, tmp_path, seed,
                        expect="WALK OK")
