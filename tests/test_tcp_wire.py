"""Cross-host TCP wire (round 24; parallel/tcp_wire.py).

Four tiers, mirroring the tentpole's layering:

* protocol units — two wire ends in one process (streams are
  per-(channel, peer), so threads stand in for processes): frame round
  trips through real kernel sockets, multi-chunk blobs, independent
  channels, counters;
* fault drills — a flipped bit ANYWHERE in the frame (length prefix,
  header, body, the seal's own tag byte) and a re-entered exchange
  round must surface as typed WireCorruption, never a hang or garbage;
  plus the chaos sites (tcp.delay / tcp.drop / tcp.partition) and the
  kill -9 mid-exchange drill (typed ActorDied long before the
  deadline);
* the FIRST true cross-host drills — 2-proc jax worlds where
  ``-mv_wire_hostname`` fakes distinct hosts on one box (selection and
  labels follow the override; frames still ride real sockets): the
  ``-mv_wire`` selection matrix, sharded-engine parity bit-exact over
  tcp vs the serial gloo world, the asymmetric-failure gloo fallback,
  and the cross-host critpath report naming WHICH host binds each
  stream;
* the remote replica subscriber whose fan-out bundles ride a dedicated
  tcp stream.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from multiverso_tpu.failsafe.errors import (ActorDied, DeadlineExceeded,
                                            WireCorruption)
from multiverso_tpu.parallel import seal
from multiverso_tpu.parallel.tcp_wire import TcpWire
from tests.test_multihost import run_two_process

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _pair(channels=1, data_bytes=4096, payload_crc=True, token="tok"):
    """Two wire ends meshed over loopback. Rank 1 (the highest) only
    accepts, so its connect() must already be parked before rank 0
    dials — the thread mirrors the install rendezvous's concurrency."""
    w0 = TcpWire(token, 0, 2, channels, data_bytes,
                 payload_crc=payload_crc)
    w1 = TcpWire(token, 1, 2, channels, data_bytes,
                 payload_crc=payload_crc)
    eps = {0: w0.listen_endpoints(), 1: w1.listen_endpoints()}
    t = threading.Thread(target=w1.connect, args=(eps,))
    t.start()
    w0.connect(eps)
    t.join(30)
    assert not t.is_alive(), "mesh bring-up deadlocked"
    return w0, w1


def _both(w0, w1, fn0, fn1, timeout=30):
    out = {}
    errs = {}

    def run(key, fn):
        try:
            out[key] = fn()
        except BaseException as exc:    # re-raised by the caller
            errs[key] = exc

    ts = [threading.Thread(target=run, args=(0, fn0)),
          threading.Thread(target=run, args=(1, fn1))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout)
    assert not any(t.is_alive() for t in ts), "wire exchange deadlocked"
    return out, errs


class TestTcpWireProtocol:
    def test_exchange_round_trip_and_multi_chunk(self):
        w0, w1 = _pair(data_bytes=4096)     # chunk cap 4096: blobs span
        try:
            for i in range(12):
                b0 = bytes([1]) * (i * 3517 % 20000)
                b1 = bytes([2]) * ((i * 2311 + 7) % 20000)
                out, errs = _both(w0, w1,
                                  lambda b=b0: w0.exchange(b, 0),
                                  lambda b=b1: w1.exchange(b, 0))
                assert not errs, errs
                assert out[0] == [b0, b1] == out[1]
        finally:
            w0.close()
            w1.close()

    def test_channels_are_independent_streams(self):
        # one driving thread PER (rank, channel), skewed round counts —
        # the sharded engine's shape (each shard owns one channel)
        w0, w1 = _pair(channels=3)
        try:
            out = {}

            def drive(w, rank, c, rounds):
                got = []
                for i in range(rounds):
                    got.append(w.exchange(b"%d:%d:%d" % (rank, c, i), c))
                out[(rank, c)] = got

            rounds = {0: 5, 1: 1, 2: 3}
            ts = [threading.Thread(target=drive, args=(w, r, c, n))
                  for r, w in ((0, w0), (1, w1))
                  for c, n in rounds.items()]
            for t in ts:
                t.start()
            for t in ts:
                t.join(30)
            assert not any(t.is_alive() for t in ts), "deadlocked"
            for c, n in rounds.items():
                for r in (0, 1):
                    assert out[(r, c)] == [
                        [b"0:%d:%d" % (c, i), b"1:%d:%d" % (c, i)]
                        for i in range(n)]
        finally:
            w0.close()
            w1.close()

    def test_empty_and_asymmetric_frames(self):
        w0, w1 = _pair()
        try:
            out, errs = _both(w0, w1,
                              lambda: w0.exchange(b"", 0),
                              lambda: w1.exchange(b"xyz", 0))
            assert not errs, errs
            assert out[0] == [b"", b"xyz"] == out[1]
        finally:
            w0.close()
            w1.close()

    def test_stats_and_counters(self):
        from multiverso_tpu.telemetry import metrics as tmetrics
        c0 = tmetrics.snapshot().get("tcp_wire.exchanges",
                                     {}).get("value", 0)
        w0, w1 = _pair()
        try:
            _both(w0, w1, lambda: w0.exchange(b"s" * 100, 0),
                  lambda: w1.exchange(b"s" * 100, 0))
            st = w0.stats()
            assert st["rounds"] == [1]
            assert st["streams"] == 1
            assert tmetrics.snapshot()["tcp_wire.exchanges"][
                "value"] >= c0 + 2
            assert w0.mem_bytes()["stream_count"] == 1
        finally:
            w0.close()
            w1.close()

    def test_next_round_bytes_survive_in_stream_buffer(self):
        # one recv may pull this round's tail together with the head of
        # the peer's NEXT round — the leftover must stay buffered and
        # complete the following exchange
        w0 = TcpWire("t", 0, 2, 1, 4096, payload_crc=True)
        try:
            b7 = b"seven" * 100
            out7, _ = w0._frames(b7, 7, 0, seal.fast_crc(b7))
            out8, _ = w0._frames(b"eight", 8, 0, seal.fast_crc(b"eight"))
            s = {"buf": bytearray(out7 + out8), "asm": None, "crc": 0,
                 "total": None, "crc_latch": 0, "chunks": 0,
                 "done_r": False}
            w0._drain_frames(0, 0, 7, s)
            assert s["done_r"] and bytes(s["asm"]) == b7
            assert bytes(s["buf"]) == bytes(out8)
            s2 = {"buf": s["buf"], "asm": None, "crc": 0, "total": None,
                  "crc_latch": 0, "chunks": 0, "done_r": False}
            w0._drain_frames(0, 0, 8, s2)
            assert s2["done_r"] and bytes(s2["asm"]) == b"eight"
        finally:
            w0.close()


class TestTcpWireFaults:
    """Bitflip-everywhere: corruption at ANY byte of the frame train
    must convert to a typed WireCorruption before any field is
    trusted — never a hang, never a garbage blob."""

    def _train(self, blob=b"Y" * 9000, rnd=7, payload_crc=True):
        w = TcpWire("t", 0, 2, 1, 4096, payload_crc=payload_crc)
        crc = seal.fast_crc(blob) if payload_crc else 0
        out, sizes = w._frames(blob, rnd, 0, crc)
        w.close()
        return w, bytearray(out), sizes

    def _drain(self, w, buf, rnd=7):
        s = {"buf": bytearray(buf), "asm": None, "crc": 0,
             "total": None, "crc_latch": 0, "chunks": 0,
             "done_r": False}
        w._drain_frames(0, 0, rnd, s)
        return s

    def test_corrupt_length_prefix_is_refused_unread(self):
        w, buf, _ = self._train()
        buf[2] = 0xFF               # flen explodes past the chunk cap
        with pytest.raises(WireCorruption, match="length prefix"):
            self._drain(w, buf)

    def test_body_bitflip_trips_the_seal(self):
        w, buf, _ = self._train()
        buf[200] ^= 0x10            # mid-chunk payload byte
        with pytest.raises(WireCorruption, match="CRC32C"):
            self._drain(w, buf)

    def test_header_bitflip_trips_the_seal(self):
        w, buf, _ = self._train()
        buf[9] ^= 0x01              # inside the packed header
        with pytest.raises(WireCorruption):
            self._drain(w, buf)

    def test_seal_tag_byte_bitflip_trips_typed(self):
        w, buf, sizes = self._train()
        buf[sizes[0] - 1] ^= 0xFF   # the first frame's seal tag byte
        with pytest.raises(WireCorruption):
            self._drain(w, buf)

    def test_round_stamp_desync_trips_typed(self):
        # a peer re-entering the exchange alone (frames stamped round
        # 7 against a reader at round 8) must surface loudly
        w, buf, _ = self._train(rnd=7)
        with pytest.raises(WireCorruption, match="desync"):
            self._drain(w, buf, rnd=8)

    def test_whole_blob_crc_catches_consistent_frame_lies(self):
        # frames individually sealed but carrying the WRONG blob CRC:
        # the whole-blob check (payload_crc) still refuses the blob
        w = TcpWire("t", 0, 2, 1, 4096, payload_crc=True)
        out, _ = w._frames(b"z" * 100, 0, 0, 0xDEADBEEF)
        w.close()
        with pytest.raises(WireCorruption, match="whole-blob"):
            self._drain(w, out, rnd=0)

    def test_live_socket_bitflip_raises_on_the_receiver(self):
        # corruption THROUGH the socket path: rank 1's outbound train
        # is poisoned at build time; rank 0 must raise typed, and the
        # crc_failures counter must tick
        from multiverso_tpu.telemetry import metrics as tmetrics
        c0 = tmetrics.snapshot().get("tcp_wire.crc_failures",
                                     {}).get("value", 0)
        w0, w1 = _pair()
        try:
            real = w1._frames

            def poisoned(blob, rnd, channel, crc):
                out, sizes = real(blob, rnd, channel, crc)
                out[len(out) // 2] ^= 0x40
                return out, sizes

            w1._frames = poisoned
            out, errs = _both(w0, w1,
                              lambda: w0.exchange(b"a" * 2000, 0,
                                                  timeout_s=10),
                              lambda: w1.exchange(b"b" * 2000, 0,
                                                  timeout_s=10))
            assert isinstance(errs.get(0), WireCorruption), (out, errs)
            assert tmetrics.snapshot()["tcp_wire.crc_failures"][
                "value"] > c0
        finally:
            w0.close()
            w1.close()


class TestTcpWireChaos:
    """The round-24 chaos sites, fired deterministically (P=1.0) on an
    in-process pair — both ends draw from the same process-wide
    schedule, so both exchanges see the fault."""

    @pytest.fixture()
    def chaos(self):
        from multiverso_tpu.utils.configure import SetCMDFlag

        def arm(spec):
            SetCMDFlag("chaos_spec", spec)
            SetCMDFlag("chaos_seed", 7)

        yield arm
        SetCMDFlag("chaos_spec", "")

    def test_tcp_delay_slows_but_never_corrupts(self, chaos):
        from multiverso_tpu.telemetry import metrics as tmetrics
        w0, w1 = _pair()
        try:
            chaos("tcp.delay:1.0@0.08")
            t0 = time.perf_counter()
            out, errs = _both(w0, w1,
                              lambda: w0.exchange(b"d0", 0,
                                                  timeout_s=10),
                              lambda: w1.exchange(b"d1", 0,
                                                  timeout_s=10))
            assert not errs, errs
            assert out[0] == [b"d0", b"d1"] == out[1]
            assert time.perf_counter() - t0 >= 0.08
            assert tmetrics.snapshot().get("chaos.tcp.delay",
                                           {}).get("value", 0) > 0
        finally:
            w0.close()
            w1.close()

    def test_tcp_drop_converts_to_deadline_not_hang(self, chaos):
        w0, w1 = _pair()
        try:
            chaos("tcp.drop:1.0")
            t0 = time.perf_counter()
            out, errs = _both(w0, w1,
                              lambda: w0.exchange(b"x" * 500, 0,
                                                  timeout_s=1.5),
                              lambda: w1.exchange(b"y" * 500, 0,
                                                  timeout_s=1.5))
            elapsed = time.perf_counter() - t0
            # each side swallowed its final frame toward the other:
            # both stall on bytes that never arrive, and the deadline
            # (NOT a hang) converts the stall, marked fatal
            for r in (0, 1):
                assert isinstance(errs.get(r), DeadlineExceeded), \
                    (out, errs)
                assert errs[r].mv_fatal
            assert elapsed < 10, "drop stalled far past the deadline"
        finally:
            w0.close()
            w1.close()

    def test_tcp_partition_severs_to_typed_actor_died(self, chaos):
        w0, w1 = _pair()
        try:
            chaos("tcp.partition:1.0")
            out, errs = _both(w0, w1,
                              lambda: w0.exchange(b"p0", 0,
                                                  timeout_s=10),
                              lambda: w1.exchange(b"p1", 0,
                                                  timeout_s=10))
            for r in (0, 1):
                assert isinstance(errs.get(r), ActorDied), (out, errs)
        finally:
            w0.close()
            w1.close()


_KILL_CHILD = r'''
import json, os, sys, time
sys.path.insert(0, sys.argv[2])
from multiverso_tpu.parallel.tcp_wire import TcpWire
epf = sys.argv[1]
w = TcpWire("kill-drill", rank=1, nprocs=2, channels=1,
            data_bytes=1 << 16)
with open(epf + ".tmp", "w") as f:
    json.dump(w.listen_endpoints(), f)
os.replace(epf + ".tmp", epf)
w.connect(None, timeout_s=30)        # highest rank: wait for the dial
w.exchange(b"round0-child", 0, timeout_s=30)
print("READY", flush=True)
time.sleep(120)                      # never enters round 1 — the
                                     # parent kill -9s us mid-exchange
'''


class TestTcpWireKillDrill:
    def test_kill_9_mid_exchange_raises_actor_died_fast(self, tmp_path):
        """kill -9 a peer while this side is parked mid-exchange: the
        kernel closes the dead process's sockets, and EOF must convert
        to a typed ActorDied immediately — long before the 30s
        deadline, and never a hang."""
        epf = str(tmp_path / "eps.json")
        child = tmp_path / "child.py"
        child.write_text(_KILL_CHILD)
        proc = subprocess.Popen(
            [sys.executable, str(child), epf, ROOT],
            env=dict(os.environ, PYTHONPATH=ROOT),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        w = None
        try:
            deadline = time.time() + 30
            while not os.path.exists(epf):
                if proc.poll() is not None or time.time() > deadline:
                    out = proc.communicate(timeout=5)[0]
                    pytest.fail(f"kill-drill child never bound:"
                                f"\n{out[-2000:]}")
                time.sleep(0.02)
            with open(epf) as f:
                eps = [tuple(e) for e in json.load(f)]
            w = TcpWire("kill-drill", rank=0, nprocs=2, channels=1,
                        data_bytes=1 << 16)
            w.connect({1: eps}, timeout_s=30)
            got = w.exchange(b"round0-parent", 0, timeout_s=30)
            assert got == [b"round0-parent", b"round0-child"]

            state = {}

            def round1():
                t0 = time.perf_counter()
                try:
                    w.exchange(b"round1", 0, timeout_s=30)
                    state["err"] = None
                except BaseException as exc:
                    state["err"] = exc
                state["s"] = time.perf_counter() - t0

            t = threading.Thread(target=round1)
            t.start()
            time.sleep(0.4)          # parked: the child never answers
            os.kill(proc.pid, signal.SIGKILL)
            t.join(20)
            assert not t.is_alive(), "exchange hung past the kill"
            assert isinstance(state["err"], ActorDied), state["err"]
            assert state["s"] < 10, (
                f"EOF took {state['s']:.1f}s to convert — the kill "
                f"must surface immediately, not ride the deadline")
        finally:
            if w is not None:
                w.close()
            if proc.poll() is None:
                proc.kill()
            proc.wait(timeout=10)


_SELECTION_PARITY_CHILD = r'''
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

# two KV tables, one a shard stream: their applies are host-local, as
# two streams in a multi-process world need (tests/test_sharded.py)
R, K, ROUNDS = 200, 20, 10

def world(shards, coord_port, want_wire):
    # loopback cross-host: the hostname override fakes distinct hosts
    # on one box, so selection takes the cross-host path while frames
    # ride real sockets through the kernel
    mv.MV_Init([f"-dist_coordinator=127.0.0.1:{coord_port}",
                f"-dist_rank={rank}", "-dist_size=2",
                f"-mv_engine_shards={shards}", "-mv_deadline_s=60",
                "-mv_wire=auto",
                "-mv_wire_hostname=node" + "AB"[rank]])
    assert multihost.wire_name() == want_wire, \
        (multihost.wire_name(), want_wire)
    assert multihost.host_label() == "node" + "AB"[rank]
    wide = mv.MV_CreateTable(KVTableOption())
    kv = mv.MV_CreateTable(KVTableOption())
    rng = np.random.default_rng(31 + rank)
    for i in range(ROUNDS):
        ids = np.sort(rng.choice(R, K, replace=False)).astype(np.int64)
        # integer-valued deltas: float32 sums of small integers are
        # exact under ANY grouping, so "bit-exact" tests the PROTOCOL
        # (no verb lost/duplicated/misrouted over tcp), not summation
        # order
        deltas = rng.integers(-4, 5, K).astype(np.float32)
        wide.AddFireForget(ids, deltas)
        kv.AddFireForget(np.array([i, 900 + rank], np.int64),
                         np.ones(2, np.float32))
    final = wide.Get(np.arange(R, dtype=np.int64))
    keys = np.array(sorted(set(list(range(ROUNDS)) + [900, 901])),
                    np.int64)
    kvv = kv.Get(keys)
    if want_wire == "tcp":
        from multiverso_tpu.telemetry import metrics as tmetrics
        snap = tmetrics.snapshot()
        assert snap.get("tcp_wire.exchanges", {}).get("value", 0) > 0, \
            "engine exchanges never rode the tcp wire"
    mv.MV_Barrier()
    mv.MV_ShutDown()
    return final, kvv

# hosts differ + 2 channels -> auto selects tcp (the sharded world)
f2, k2 = world(2, port, "tcp")
# hosts differ + ONE channel -> auto stays on gloo (the loud
# fallback): this world doubles as the SERIAL reference
f1, k1 = world(1, int(port) + 1, "gloo")
np.testing.assert_array_equal(f1, f2)
np.testing.assert_array_equal(k1, k2)
print(f"child {rank} TCP-PARITY OK", flush=True)
'''


_ASYM_FAIL_CHILD = r'''
import os, sys
rank, port = int(sys.argv[1]), sys.argv[2]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.parallel import multihost

if rank == 0:
    # simulate a listener bind / mesh failure on ONE rank only: the
    # whole world must agree to fall back to gloo (the vote protocol),
    # never desync its collective stream
    from multiverso_tpu.parallel import tcp_wire

    class _Boom(tcp_wire.TcpWire):
        def __init__(self, *a, **k):
            raise OSError("simulated tcp listener bind failure")

    tcp_wire.TcpWire = _Boom

mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2", "-mv_engine_shards=2", "-mv_wire=auto",
            "-mv_wire_hostname=node" + "AB"[rank]])
assert multihost.wire_name() == "gloo", multihost.wire_name()
from multiverso_tpu.tables import MatrixTableOption
t = mv.MV_CreateTable(MatrixTableOption(num_rows=32, num_cols=2))
ids = np.arange(4, dtype=np.int32)
for _ in range(4):
    t.AddRows(ids, np.ones((4, 2), np.float32))
np.testing.assert_array_equal(t.GetRows(ids), np.full((4, 2), 8.0))
mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} TCP-ASYM-FALLBACK OK", flush=True)
'''


_CRITPATH_CHILD = r'''
import os, sys
rank, port, dumpdir = int(sys.argv[1]), sys.argv[2], sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.tables import MatrixTableOption
from multiverso_tpu.parallel import multihost

# -mv_wire=tcp FORCES the wire even for a single-channel world
mv.MV_Init([f"-dist_coordinator=127.0.0.1:{port}", f"-dist_rank={rank}",
            "-dist_size=2", "-mv_wire=tcp", "-mv_deadline_s=60",
            "-mv_wire_hostname=node" + "AB"[rank]])
assert multihost.wire_name() == "tcp", multihost.wire_name()
R, C = 128, 8
table = mv.MV_CreateTable(MatrixTableOption(num_rows=R, num_cols=C))
rng = np.random.default_rng(5 + rank)
for i in range(10):
    ids = np.sort(rng.choice(R, 16, replace=False)).astype(np.int32)
    table.AddRows(ids, rng.standard_normal((16, C)).astype(np.float32))
table.GetRows(np.arange(R, dtype=np.int32))
from multiverso_tpu.telemetry import flight
flight.dump(os.path.join(dumpdir, f"flight_rank{rank}.jsonl"))
mv.MV_Barrier()
mv.MV_ShutDown()
print(f"child {rank} TCP-CRITPATH OK", flush=True)
'''


class TestTcpWireWorlds:
    def test_auto_selection_matrix_and_sharded_parity_over_tcp(
            self, tmp_path):
        """auto picks tcp when hosts differ AND channels > 1, gloo when
        one channel suffices — and the 2-proc sharded engine over tcp
        is bit-exact vs the serial gloo world."""
        run_two_process(_SELECTION_PARITY_CHILD, tmp_path,
                        expect="TCP-PARITY OK")

    def test_one_rank_tcp_failure_degrades_whole_world(self, tmp_path):
        run_two_process(_ASYM_FAIL_CHILD, tmp_path,
                        expect="TCP-ASYM-FALLBACK OK")

    def test_cross_host_critpath_names_binding_host(self, tmp_path):
        """The cross-host critpath report must name WHICH HOST binds
        each stream, not just which rank — the flight headers carry the
        (overridden) host labels and correlate threads them through
        windows, streams and the text verdict."""
        from multiverso_tpu.telemetry import critpath
        run_two_process(_CRITPATH_CHILD, tmp_path, str(tmp_path),
                        expect="TCP-CRITPATH OK")
        rep = critpath.correlate(
            [str(tmp_path / "flight_rank0.jsonl"),
             str(tmp_path / "flight_rank1.jsonl")])
        assert rep["hosts"] == {0: "nodeA", 1: "nodeB"}, rep["hosts"]
        assert rep["n_windows"] > 0, rep.get("note")
        for w in rep["windows"]:
            assert w["binding_host"] in ("nodeA", "nodeB"), w
            assert w["binding_host"] == "node" + "AB"[w["binding_rank"]]
        for s in rep["streams"].values():
            assert s["dominant_host"] == \
                "node" + "AB"[s["dominant_rank"]], s
        text = critpath.report_text(rep)
        assert "nodeA" in text or "nodeB" in text, text


class TestReplicaTcpSubscriber:
    """A replica subscriber whose fan-out bundles ride a dedicated tcp
    stream: the reader binds its listener BEFORE joining (the endpoint
    rides the join token), the publisher's first ship dials it, and
    lookups bit-match the trainer."""

    def test_tcp_replica_bit_matches_and_deltas_stay_small(
            self, tmp_path):
        import multiverso_tpu as mv
        from multiverso_tpu.replica.replica import ReplicaClient
        from multiverso_tpu.tables import MatrixTableOption
        from multiverso_tpu.telemetry import metrics as tmetrics
        from tests.test_replica import spawn_replica, wait_version

        R, C = 3000, 16
        mv.MV_Init(["-mv_replica_fanout=true"])
        proc = None
        try:
            from multiverso_tpu.replica import publisher
            ep = publisher.publisher_endpoint()
            assert ep is not None
            mat = mv.MV_CreateTable(MatrixTableOption(num_rows=R,
                                                      num_cols=C))
            rng = np.random.default_rng(0)
            mat.AddRows(np.arange(R, dtype=np.int32),
                        rng.standard_normal((R, C)).astype(np.float32))
            v1 = mv.MV_PublishSnapshot()
            proc, st = spawn_replica(ep, tmp_path, mode="tcp")
            rc = ReplicaClient("127.0.0.1", st["serve_port"])
            wait_version(rc, v1)

            # the subscription really is tcp-mode, and the bundles rode
            # the wire (the trainer-side publisher counts its sends)
            rep = publisher.status_report()
            modes = {s["rid"]: s["mode"] for s in rep["subscribers"]}
            assert modes[st["rid"]] == "tcp", rep
            assert tmetrics.snapshot().get(
                "tcp_wire.exchanges", {}).get("value", 0) > 0, \
                "fan-out bundles never rode the tcp wire"

            def counter(name):
                return tmetrics.snapshot().get(name, {}).get("value", 0)

            base_bytes = counter("replica.fanout_bytes")
            assert base_bytes > R * C * 4

            # 1% churn -> the delta must be tiny vs the base
            sel = rng.choice(R, R // 100, replace=False).astype(np.int32)
            mat.AddRows(sel, np.ones((len(sel), C), np.float32))
            v2 = mv.MV_PublishSnapshot()
            wait_version(rc, v2)
            delta_bytes = counter("replica.fanout_bytes") - base_bytes
            assert 0 < delta_bytes <= 0.10 * base_bytes, (
                f"delta fan-out {delta_bytes}B vs base {base_bytes}B")

            # bit-match: both live versions
            ids = np.sort(rng.choice(R, 64, replace=False))
            for v in (v1, v2):
                got = rc.lookup(0, ids, version=v)
                want = mv.MV_ServingLookup(mat, ids, version=v)
                assert np.array_equal(got, want), f"matrix v{v}"
        finally:
            if proc is not None:
                proc.terminate()
                proc.wait(timeout=10)
            mv.MV_ShutDown()
