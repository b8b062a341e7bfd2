"""Multi-host (multi-process) runtime wiring over ``jax.distributed``.

The reference scales across machines with MPI/ZMQ point-to-point messaging
(SURVEY.md §2c): every process runs worker+server actors and Get/Add
requests cross the network per table shard. The TPU-native equivalent is a
**multi-controller SPMD job**: one process per host, all processes
participating in a single global device mesh, parameter shards laid across
every host's HBM, and the "network" being XLA collectives over ICI (intra
slice) / DCN (across slices) — the scaling-book model.

The SPMD constraint this imposes (and the honest behavioral mapping):

* computations on globally-sharded arrays are **collective** — every
  process must issue the same program in the same order. Table verbs in
  multihost mode therefore follow the *collective contract*: every process
  calls the same Get/Add sequence (normal SPMD training loops — and the
  device plane — do this naturally).
* the reference's *asynchrony* (workers never wait for each other) lives
  **within** each host among its worker threads, exactly as in the 1-host
  world; cross-host progress is synchronous at collective boundaries. This
  is the documented reinterpretation SURVEY.md §7 anticipates ("bounded
  async via microbatched rounds") — on TPU fabric, lockstep collectives are
  the fast path, not a compromise.

What this module provides:

* ``maybe_initialize`` — bring up ``jax.distributed`` from flags
  (``-dist_coordinator/-dist_rank/-dist_size``) or automatic TPU-pod
  detection (``-multihost=auto`` uses it only when the env indicates a
  multi-process job; ``on`` forces; ``off`` never).
* ``process_index/process_count`` — identity (Zoo rank/size).
* ``host_barrier`` — cross-host barrier (device-level sync over the global
  mesh), the Controller-barrier equivalent (reference controller.cpp:12-36).
* ``host_allreduce_sum`` — cross-host elementwise sum of a host numpy
  array, used by ``MV_Aggregate`` to extend the in-process rendezvous
  allreduce across hosts (reference MV_Aggregate → MPI_Allreduce,
  src/multiverso.cpp:53-56).
* ``broadcast_from_master`` — host-0 value to all hosts (the binding's
  master-initializes convention, reference tables.py:49-58).

All of them degrade to no-ops / identity in a single-process job, so the
1-host world (tests, the reference's unittest fixture pattern) runs the
same code paths.
"""

from __future__ import annotations

import os
import time as _time
from typing import Optional

import numpy as np

from multiverso_tpu.utils.configure import (GetFlag, MV_DEFINE_int,
                                            MV_DEFINE_string)
from multiverso_tpu.utils.log import CHECK, Log

MV_DEFINE_string("multihost", "auto", "multi-process init: auto / on / off")
# reference ZMQ deployment flags (zmq_net.h:20-21), kept for flag parity:
# a machine file maps line N -> rank N endpoints; on TPU it feeds the same
# explicit jax.distributed wiring MV_NetBind/MV_NetConnect use
MV_DEFINE_string("machine_file", "",
                 "hosts file, one endpoint per line = rank order "
                 "(reference ZMQ -machine_file; feeds net wiring)")
MV_DEFINE_int("port", 55555,
              "default port when a machine-file line has none "
              "(reference ZMQ -port)")
MV_DEFINE_string("dist_coordinator", "",
                 "coordinator address host:port (jax.distributed)")
MV_DEFINE_int("dist_rank", -1, "this process index (jax.distributed)")
MV_DEFINE_int("dist_size", -1, "total process count (jax.distributed)")
# Round 12 — pluggable host wire (the reference's ZMQ-vs-MPI backend
# split, PAPER.md L2: transports are deployment choices, not protocol
# changes). "auto": same-host worlds ride the shared-memory wire
# (parallel/shm_wire.py — gloo measured ~410 MB/s between two
# processes of ONE machine; shm is a memcpy), cross-host worlds take
# the framed tcp wire (round 24, parallel/tcp_wire.py) when the
# engine/replica asked for more than one exchange channel, else gloo.
# "gloo" forces the socket allgather; "shm"/"tcp" REQUIRE their wire
# and CHECK-fail when it cannot come up.
MV_DEFINE_string("mv_wire", "auto",
                 "windowed-engine host wire: auto (shm when every rank "
                 "shares a host; tcp when hosts differ and >1 channel "
                 "is needed; else gloo) / shm (require) / tcp "
                 "(require) / gloo")
# Round 24 — the loopback cross-host drills: CI has one box, but the
# cross-host selection/labeling code path must still be exercised for
# real. The override changes THIS rank's host IDENTITY (wire
# selection votes, telemetry + critpath labels) while dialing always
# rides the genuinely advertised endpoints — the honest split between
# "which code path runs" and "which sockets carry bytes".
MV_DEFINE_string("mv_wire_hostname", "",
                 "override this rank's host identity in wire selection "
                 "and telemetry/critpath labels (loopback cross-host "
                 "drills fake distinct hosts on one box; dialing still "
                 "rides real endpoints). Empty = the real hostname")
MV_DEFINE_int("mv_shm_ring_bytes", 4 << 20,
              "shared-memory wire: per-(channel, rank) data area bytes "
              "(frames larger than this chunk through it)")
# Round 12 — elastic follow-on 4 (ROADMAP): the PJRT coordination
# service declares a silent member dead after ~100s of missed
# heartbeats (10s interval x 10 misses) and then tears the survivors
# down — a long-lived SHRUNK world (elastic plane, the dead member
# never returns) must outlive that corpse detection. MV_Init hands
# this budget to jax.distributed.initialize(heartbeat_timeout_seconds=).
# 0 = leave the runtime default; -mv_elastic worlds default to 600s.
MV_DEFINE_int("mv_pjrt_heartbeat_s", 0,
              "PJRT coordination-service liveness budget in seconds "
              "(missed-heartbeat window before a silent member is "
              "declared dead); 0 = runtime default (~100s), or 600 "
              "when -mv_elastic is on")

_initialized = False
_owns_runtime = False   # True only when WE called jax.distributed.initialize

#: observability: HOST collective rounds issued through this module (and
#: mesh.fetch's reassembly allgather). The r4 verdict's scale-out
#: critique was "one host collective per table verb"; the windowed
#: engine protocol (sync/server.py) is judged by THIS counter per verb
#: (tests/test_windowed_multihost.py, test_serving.py, test_replica.py).
#: XLA-level collectives (psum etc. inside jit programs) ride ICI and
#: are deliberately not counted — they are the fast path, not the
#: protocol cost. Seconds inside an exchange are the phase stamps'
#: (_stamp_exchange) and the histograms server.wire.{encode,decode}_s.
STATS = {"host_collective_rounds": 0}


def note_collective(n: int = 1) -> None:
    STATS["host_collective_rounds"] += n


#: per-call timing of the LAST capped_exchange on this process — the
#: engine's phase stamping (round 11, sync/server.py) reads it right
#: after its window exchange returns, on the same thread, to split the
#: time BLOCKED IN THE COLLECTIVE (``coll_s``) from local staging work
#: and to anchor cross-rank clock alignment on the exchange-done wall
#: stamp (every rank leaves the same allgather at ~the same instant — a
#: free sync pulse per window; telemetry/critpath.py). The dict is
#: replaced atomically per call (readers see an old or a new record,
#: never a torn one); cost when nobody reads it is four float stores.
_exchange_last = {"enter_m": 0.0, "done_m": 0.0, "done_w": 0.0,
                  "coll_s": 0.0}


def _stamp_exchange(enter_m: float, coll_s: float, done_m: float,
                    done_w: float) -> None:
    global _exchange_last
    # mv-lint: ok(cross-domain-state): one atomic dict-REF store per exchange (the torn-read-free design documented above); the worker-domain reachability is the MA-mode aggregate path, and MA worlds run no engine thread
    _exchange_last = {"enter_m": enter_m, "done_m": done_m,
                      "done_w": done_w, "coll_s": coll_s}


def last_exchange_stats() -> dict:
    """Timing of this process's most recent :func:`capped_exchange`:
    ``enter_m``/``done_m`` (perf_counter), ``done_w`` (wall clock at
    collective exit — the rendezvous pulse) and ``coll_s`` (seconds
    blocked inside the collective op(s), excluding local staging)."""
    return _exchange_last


# -- elastic membership groups (round 10, elastic/) ----------------------
# The boot world is jax.distributed's: process_index/process_count are
# frozen at init, and every host-byte exchange above rides gloo
# allgathers over ALL boot processes. An elastic epoch installs a GROUP
# — the subset of boot ranks currently in the world — and the exchange
# layer re-forms around it: singleton groups take the single-process
# identity paths (no collectives at all, which is also what makes a
# survivor's world sound after a peer died mid-allgather: the abandoned
# gloo stream is simply never touched again), and multi-member groups
# ride the coordinator-relayed exchange the elastic plane provides
# (gloo cannot subset the boot world, and after ANY transition the
# boot-world collective stream can no longer be trusted to be aligned).
# process_index()/process_count() deliberately keep their boot meaning
# (device ownership, forensic rank identity); membership-aware code
# asks world_rank()/world_size().

class Group:
    """One membership epoch's view of the world.

    ``members`` are boot ranks, sorted; ``exchange(blob, key)`` is the
    group's allgather-bytes primitive (None = identity / unused for
    singleton groups); ``barrier(name)`` its rendezvous."""

    def __init__(self, epoch: int, members, exchange=None, barrier=None):
        self.epoch = int(epoch)
        self.members = tuple(sorted(int(m) for m in members))
        self._exchange = exchange
        self._barrier = barrier

    @property
    def size(self) -> int:
        return len(self.members)

    def rank(self) -> int:
        """This process's position in the member list, -1 if departed."""
        try:
            return self.members.index(process_index())
        except ValueError:
            return -1

    def _require_member(self, what: str) -> None:
        if self.rank() < 0:
            from multiverso_tpu.failsafe.errors import MembershipChanged
            raise MembershipChanged(
                f"{what} from a departed member", epoch=self.epoch,
                members=self.members, departed=(process_index(),))

    def exchange(self, blob: bytes, key) -> list:
        if self.size <= 1 and self.rank() >= 0:
            return [blob]
        self._require_member("collective exchange")
        CHECK(self._exchange is not None,
              "multi-member elastic group without an exchange transport")
        note_collective()
        return self._exchange(blob, key)

    def barrier(self, name: str) -> None:
        if self.size <= 1 and self.rank() >= 0:
            return
        self._require_member("collective barrier")
        CHECK(self._barrier is not None,
              "multi-member elastic group without a barrier transport")
        note_collective()
        self._barrier(name)


_group: Optional[Group] = None

# -- pluggable host wire (round 12 shm, round 24 tcp) --------------------
#: the installed transport behind capped_exchange (None = gloo). Boot
#: world only: elastic groups (installed above) take precedence, and a
#: membership transition never routes through a wire the dead member
#: still owns segments of.
_wire = None


def active_wire():
    """The installed host wire (ShmWire same-host / TcpWire
    cross-host — round 24), or None when exchanges ride gloo."""
    return _wire


def wire_name() -> str:
    """Label of the transport capped_exchange currently rides —
    dashboards/healthz; 'relay' while an elastic group is installed."""
    if _group is not None and _group.size > 1:
        return "relay"
    if _wire is not None:
        return getattr(_wire, "name", "shm")
    return "gloo" if (_initialized and process_count() > 1) else "local"


def host_label() -> str:
    """This rank's host identity for wire selection and telemetry
    labels: ``-mv_wire_hostname`` when set (the loopback cross-host
    drills fake distinct hosts on one box — selection and labels
    follow the override while dialing rides real endpoints), else the
    real hostname. Registry-safe (flight dumps run at teardown)."""
    import socket
    try:
        v = str(GetFlag("mv_wire_hostname"))
    except Exception:       # registry torn down
        v = ""
    if v:
        return v
    try:
        return socket.gethostname()
    except OSError:
        return "localhost"


def wire_channels() -> int:
    """Independent exchange channels the active transport offers. The
    gloo allgather is ONE globally-ordered collective stream (channel
    0 only); the shm wire offers one stream per channel — what lets
    engine shards exchange concurrently in a multi-process world."""
    return _wire.channels if _wire is not None else 1


def maybe_install_wire(channels: int) -> str:
    """Select + install the host wire for this world (Zoo.Start, after
    jax.distributed is up, BEFORE the engine starts). One gloo
    rendezvous exchanges (host label, nonce) across the boot world:
    same-host worlds ride the shm wire, hosts-differ worlds take the
    tcp wire when more than one channel is needed (``-mv_wire=tcp``
    forces it regardless), gloo is the loud fallback. Either wire is
    proven by a smoke exchange before anything trusts it, and ANY
    setup failure degrades the WHOLE world to gloo symmetrically
    (CHECK-fails only under ``-mv_wire=shm``/``tcp``, where the
    fallback was explicitly refused). Returns the active transport
    name."""
    global _wire
    mode = str(GetFlag("mv_wire")).lower()
    CHECK(mode in ("auto", "shm", "tcp", "gloo"),
          f"-mv_wire must be auto/shm/tcp/gloo, got {mode!r}")
    if not _initialized or process_count() <= 1 or mode == "gloo":
        return wire_name()
    if _wire is not None:
        return getattr(_wire, "name", "shm")
    import secrets
    info = host_allgather_objects(
        (host_label(), secrets.token_hex(4)))
    hosts = [h for h, _ in info]
    token = info[0][1]          # rank 0's nonce names the session
    spans_hosts = any(h != hosts[0] for h in hosts)
    if mode == "tcp" or (spans_hosts and mode == "auto"
                         and max(1, int(channels)) > 1):
        return _install_tcp_wire(mode, token, max(1, int(channels)),
                                 hosts)
    if spans_hosts:
        CHECK(mode != "shm",
              f"-mv_wire=shm but ranks span hosts: {hosts}")
        Log.Debug("multihost: ranks span hosts (%s) and %d channel(s) "
                  "suffice — staying on gloo (-mv_wire=tcp forces the "
                  "tcp wire)", hosts, max(1, int(channels)))
        return "gloo"
    from multiverso_tpu.parallel import shm_wire

    # Every rank runs the IDENTICAL gloo collective sequence below —
    # a local failure becomes an ok=False VOTE instead of a skipped
    # round, because a rank that raises past a matched collective
    # leaves its peers permanently off-by-one on the gloo stream (an
    # asymmetric create failure must degrade the WHOLE world to gloo,
    # not desync it). A failed vote at any step: everyone cleans up
    # and returns gloo; the vote round itself realigned the world.
    # payload_crc=False: every engine blob already carries the
    # failsafe wire's CRC32 trailer (parallel/wire.py, verified before
    # parsing) — a second full-blob CRC pass would halve the wire's
    # bandwidth to guard what is already guarded. The frame headers
    # stay CRC'd and truncation stays structurally detected
    # (shm_wire.py docstring).
    state = {"wire": None, "exc": None}
    try:
        state["wire"] = shm_wire.ShmWire(
            token, process_index(), process_count(),
            max(1, int(channels)), int(GetFlag("mv_shm_ring_bytes")),
            payload_crc=False)
    except Exception as e:
        state["exc"] = e

    def _vote(step: str) -> bool:
        votes = host_allgather_objects(state["exc"] is None)
        if all(votes):
            return True
        if state["wire"] is not None:
            state["wire"].close()
        CHECK(mode != "shm",
              f"-mv_wire=shm but the wire failed to come up at "
              f"{step}: {state['exc']!r} (votes {votes})")
        Log.Error("multihost: shm wire setup failed at %s on rank(s) "
                  "%s (%r here) — falling back to gloo", step,
                  [i for i, v in enumerate(votes) if not v],
                  state["exc"])
        return False

    if not _vote("segment create"):
        return "gloo"
    try:        # segments exist on every rank (the vote proved it)
        state["wire"].attach_peers()
    except Exception as e:
        state["exc"] = e
    if not _vote("peer attach"):
        return "gloo"
    try:
        hello = b"mv-shm-hello-%d" % process_index()
        got = state["wire"].exchange(hello, 0)
        CHECK(got == [b"mv-shm-hello-%d" % r
                      for r in range(process_count())],
              f"shm wire smoke exchange returned {got!r}")
    except Exception as e:
        state["exc"] = e
    if not _vote("smoke exchange"):
        return "gloo"
    _wire = state["wire"]
    Log.Info("multihost: same-host shared-memory wire up — %d channels "
             "x %d MiB (token %s)", _wire.channels, _wire.cap >> 20,
             token)
    return "shm"


def _install_tcp_wire(mode: str, token: str, channels: int,
                      hosts) -> str:
    """The tcp leg of maybe_install_wire: bind listeners, allgather
    (ok, endpoints) in ONE collective round, dial the mesh, vote, and
    smoke-exchange before install. The vote protocol is the shm path's,
    verbatim in shape: every rank runs the IDENTICAL collective
    sequence, so an asymmetric local failure becomes an ok=False vote
    that degrades the WHOLE world to gloo instead of desyncing the
    boot collective stream. payload_crc=False for the same reason as
    shm: engine blobs arrive pre-sealed (parallel/seal.py) and the
    frame layer's own seal still guards headers + chunks."""
    global _wire
    from multiverso_tpu.parallel import tcp_wire
    state = {"wire": None, "exc": None}
    try:
        state["wire"] = tcp_wire.TcpWire(
            token, process_index(), process_count(), channels,
            int(GetFlag("mv_shm_ring_bytes")), payload_crc=False)
    except Exception as e:
        state["exc"] = e

    def _vote(step: str) -> bool:
        votes = host_allgather_objects(state["exc"] is None)
        if all(votes):
            return True
        if state["wire"] is not None:
            state["wire"].close()
        CHECK(mode != "tcp",
              f"-mv_wire=tcp but the wire failed to come up at "
              f"{step}: {state['exc']!r} (votes {votes})")
        Log.Error("multihost: tcp wire setup failed at %s on rank(s) "
                  "%s (%r here) — falling back to gloo", step,
                  [i for i, v in enumerate(votes) if not v],
                  state["exc"])
        return False

    # bind vote + endpoint rendezvous in ONE collective round
    eps = (state["wire"].listen_endpoints()
           if state["wire"] is not None else None)
    votes = host_allgather_objects((state["exc"] is None, eps))
    if not all(ok for ok, _ in votes):
        if state["wire"] is not None:
            state["wire"].close()
        CHECK(mode != "tcp",
              f"-mv_wire=tcp but the wire failed to bind its "
              f"listeners: {state['exc']!r}")
        Log.Error("multihost: tcp wire listener bind failed on "
                  "rank(s) %s (%r here) — falling back to gloo",
                  [i for i, (ok, _) in enumerate(votes) if not ok],
                  state["exc"])
        return "gloo"
    world_eps = {r: e for r, (_, e) in enumerate(votes)}
    try:
        state["wire"].connect(world_eps, timeout_s=30.0)
    except Exception as e:
        state["exc"] = e
    if not _vote("mesh connect"):
        return "gloo"
    try:
        hello = b"mv-tcp-hello-%d" % process_index()
        got = state["wire"].exchange(hello, 0, timeout_s=30.0)
        CHECK(got == [b"mv-tcp-hello-%d" % r
                      for r in range(process_count())],
              f"tcp wire smoke exchange returned {got!r}")
    except Exception as e:
        state["exc"] = e
    if not _vote("smoke exchange"):
        return "gloo"
    _wire = state["wire"]
    Log.Info("multihost: cross-host tcp wire up — %d channels x %d "
             "KiB chunks, hosts %s (token %s)", _wire.channels,
             _wire.chunk >> 10, sorted(set(hosts)), token)
    return "tcp"


def close_wire() -> None:
    """Tear the installed wire down (Zoo.Stop / net_reset). Idempotent;
    own segments are unlinked."""
    global _wire
    w, _wire = _wire, None
    if w is not None:
        w.close()


#: collective isolation (elastic rebuild_world): the host-byte exchange
#: layer answers as a single-member world while a transition fence
#: rebuilds tables — constructors re-run boot-time agreement
#: collectives (e.g. SparseMatrixTable's -num_workers check), but the
#: agreement was already established at boot and the fence has no
#: matched peer round to pair them with. world_rank()/world_size() are
#: NOT isolated: the rebuilt tables must bind the new view's identity.
_isolated = False


class collective_isolation:
    def __enter__(self):
        global _isolated
        self._prev = _isolated
        _isolated = True
        return self

    def __exit__(self, *exc):
        global _isolated
        _isolated = self._prev


#: a boot-world member DIED (silent death, elastic shrink): the
#: jax.distributed runtime's shutdown barrier would block on the dead
#: task and the coordination client then TERMINATES the survivor —
#: net_finalize skips the runtime shutdown instead (the process exit
#: reaps it)
_boot_world_broken = False


def mark_boot_world_broken() -> None:
    global _boot_world_broken
    if not _boot_world_broken:
        _boot_world_broken = True
        Log.Error("multihost: a boot-world member died — the "
                  "jax.distributed runtime will not be shut down "
                  "cleanly (survivors skip its shutdown barrier)")


def install_group(group: Optional[Group]) -> None:
    """Install the membership view every exchange routes through from
    now on (None restores the boot world). Called by the elastic plane
    at an epoch transition — on the engine thread, at the fenced stream
    position, so no exchange is in flight across the swap."""
    global _group
    _group = group
    if group is not None:
        Log.Info("multihost: membership epoch %d installed — members %s "
                 "(this process %s)", group.epoch, list(group.members),
                 "rank %d" % group.rank() if group.rank() >= 0
                 else "DEPARTED")


def current_group() -> Optional[Group]:
    return _group


def membership_epoch() -> int:
    """The installed membership epoch (0 = boot world)."""
    return _group.epoch if _group is not None else 0


def world_size() -> int:
    """Active member count of the CURRENT world (boot process count
    until an elastic epoch is installed)."""
    if _group is not None:
        return _group.size
    return process_count() if _initialized else 1


def world_rank() -> int:
    """This process's rank in the CURRENT world ordering (= boot rank
    until an elastic epoch is installed); -1 when this process has
    departed the world."""
    if _group is not None:
        return _group.rank()
    return process_index() if _initialized else 0

# Explicit-endpoint bring-up state (MV_NetBind / MV_NetConnect): the
# launcher-free deployment path. The reference's ZMQ transport let a
# process declare its own (rank, endpoint) and the full world without MPI
# (zmq_net.h:64-110); the TPU equivalent wires the same two declarations
# into jax.distributed — rank 0's endpoint IS the coordinator.
_net_rank: Optional[int] = None
_net_endpoint: Optional[str] = None
_net_world: Optional[dict] = None  # rank -> endpoint


def net_bind(rank: int, endpoint: str) -> int:
    """Declare THIS process's rank and endpoint (reference
    ZMQNetWrapper::Bind, zmq_net.h:64-81). Must precede MV_Init. For
    rank 0 the endpoint is the coordinator address the whole world
    rendezvouses on (net_connect cross-checks its rank-0 entry against
    it); other ranks' endpoints are identity records, matching the
    reference where every rank binds its own recv socket."""
    global _net_rank, _net_endpoint, _net_world
    if _initialized:
        Log.Error("MV_NetBind after the distributed runtime is up")
        return -1
    try:
        rank, endpoint = int(rank), str(endpoint)
    except (TypeError, ValueError):
        return -1
    if rank < 0 or not endpoint:
        return -1
    _net_rank = rank
    _net_endpoint = endpoint
    # re-binding invalidates a previously declared world: its validation
    # (rank membership, rank-0 endpoint cross-check) was against the old
    # identity — require a fresh MV_NetConnect
    _net_world = None
    return 0


def net_connect(ranks, endpoints) -> int:
    """Declare the full world as parallel (ranks, endpoints) lists
    (reference ZMQNetWrapper::Connect, zmq_net.h:83-110). Requires a prior
    net_bind; this process's bound rank must appear in ``ranks``. The
    next MV_Init brings up jax.distributed from this wiring."""
    global _net_world
    if _initialized:
        Log.Error("MV_NetConnect after the distributed runtime is up")
        return -1
    if _net_rank is None:
        Log.Error("MV_NetConnect before MV_NetBind")
        return -1
    try:
        ranks = [int(r) for r in ranks]
        endpoints = [str(e) for e in endpoints]
    except (TypeError, ValueError):
        return -1  # malformed declarations return -1 like every other error
    if len(ranks) != len(endpoints) or not ranks:
        return -1
    if sorted(ranks) != list(range(len(ranks))):
        # jax.distributed numbers processes 0..n-1; gaps or duplicates
        # would crash or hang the rendezvous later — reject at declaration
        Log.Error("MV_NetConnect ranks must be exactly 0..n-1, got %s",
                  ranks)
        return -1
    world = dict(zip(ranks, endpoints))
    if _net_rank not in world:
        Log.Error("MV_NetConnect world must contain the bound rank")
        return -1
    if _net_rank == 0 and world[0] != _net_endpoint:
        # rank 0's bind endpoint IS the coordinator it will listen on; a
        # mismatching connect entry would make the world rendezvous on an
        # address nothing binds
        Log.Error("rank 0 bind endpoint %s != connect entry %s",
                  _net_endpoint, world[0])
        return -1
    _net_world = world
    return 0


def net_reset() -> None:
    """Forget explicit wiring (tests / MV_ShutDown symmetry). Also
    clears the standing exchange caps: a NEW world may mix reused
    interpreters (evolved caps) with fresh ranks (defaults), and
    mismatched caps mean mismatched allgather buffer shapes — caps must
    restart from defaults on every world, like the engine's per-instance
    _mh_caps do. Also forgets any installed elastic membership group —
    a new world starts at epoch 0 (boot membership)."""
    global _net_rank, _net_endpoint, _net_world, _group
    _net_rank = _net_endpoint = _net_world = None
    _group = None
    _OBJ_CAPS.clear()
    close_wire()    # a new world re-selects (and re-tokens) its wire


def net_finalize() -> None:
    """MV_NetFinalize: forget declarations AND shut down jax.distributed
    when THIS runtime initialized it (reference finalizes its transport,
    src/multiverso.cpp:66-68). A runtime the user brought up themselves
    (maybe_initialize merely adopted it) is left alone — finalizing it
    would kill their coordinator under them. Safe to call repeatedly; a
    shutdown failure (e.g. live computations) logs and leaves the
    runtime up."""
    global _initialized, _owns_runtime
    net_reset()
    if not _initialized or not _owns_runtime:
        return
    if _boot_world_broken:
        # a dead boot member can never reach the runtime's shutdown
        # barrier; entering it would hang this survivor and then
        # TERMINATE it (coordination client fatal-error path). Leave
        # the runtime to process exit.
        Log.Info("net_finalize: boot world broken — skipping "
                 "jax.distributed.shutdown()")
        _initialized = False
        _owns_runtime = False
        return
    import jax
    try:
        jax.distributed.shutdown()
        _initialized = False
        _owns_runtime = False
    except Exception as exc:  # pragma: no cover - runtime-state specific
        Log.Error("net_finalize: jax.distributed.shutdown failed: %r", exc)


def _split_endpoint(ep: str):
    """host[:port] -> (host, port_or_None); IPv6 uses [addr]:port."""
    if ep.startswith("["):
        host, _, rest = ep[1:].partition("]")
        return host, (rest[1:] if rest.startswith(":") else None)
    host, sep, port = ep.rpartition(":")
    if sep and port.isdigit() and ":" not in host:
        return host, port
    return ep, None  # no port (or a bare IPv6 literal)


def _parse_machine_file(path: str) -> list:
    """Hosts file -> rank-ordered endpoint list (reference
    ParseMachineFile, zmq_net.h:236-258): one host[:port] per line
    (IPv6 as [addr]:port), blanks/comments skipped, the ``-port`` flag
    filling missing ports. Missing/empty files are loud errors — a
    misconfigured cluster must never silently run single-process."""
    default_port = int(GetFlag("port"))
    CHECK(os.path.exists(path), f"-machine_file not found: {path!r}")
    endpoints = []
    with open(path) as f:
        for line in f:
            ep = line.strip()
            if not ep or ep.startswith("#"):
                continue
            host, port = _split_endpoint(ep)
            if port is None:
                port = default_port
            endpoints.append(f"[{host}]:{port}" if ":" in host
                             else f"{host}:{port}")
    CHECK(endpoints, f"-machine_file {path!r} lists no endpoints")
    return endpoints


def _match_local_rank(endpoints: list):
    """This host's rank = the unique machine-file line resolving to a
    local address (reference net_util local-IP matching). None when no
    line — or more than one — matches (same-host multi-process needs an
    explicit -dist_rank, exactly as ambiguous for the reference)."""
    import socket
    local = {"127.0.0.1", "::1"}
    try:
        local.update(info[4][0] for info in socket.getaddrinfo(
            socket.gethostname(), None))
    except OSError:
        pass
    matches = []
    for i, ep in enumerate(endpoints):
        host = _split_endpoint(ep)[0]
        try:
            addrs = {info[4][0]
                     for info in socket.getaddrinfo(host, None)}
        except OSError:
            continue
        if addrs & local or host == socket.gethostname():
            matches.append(i)
    return matches[0] if len(matches) == 1 else None


def _env_says_multiprocess() -> bool:
    """TPU-pod/cluster env autodetection (mirrors what
    jax.distributed.initialize() itself can infer)."""
    if (os.environ.get("JAX_COORDINATOR_ADDRESS")
            or os.environ.get("COORDINATOR_ADDRESS")
            or os.environ.get("MEGASCALE_COORDINATOR_ADDRESS")):
        return True
    # Cloud TPU multi-host slices advertise their worker set directly
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    return len([h for h in hosts.split(",") if h.strip()]) > 1


def _enable_cpu_collectives() -> None:
    """Opt the CPU backend into cross-process collectives (gloo) before
    the backend exists. jax's default CPU collectives implementation is
    ``'none'``, under which EVERY multi-process computation — including
    the ``device_put`` equality check inside table creation — fails with
    "Multiprocess computations aren't implemented on the CPU backend";
    a 2-process CPU world (tests, single-host bring-up) therefore needs
    gloo. Only applies when the job
    explicitly targets CPU (``jax_platforms``/``JAX_PLATFORMS``): TPU
    pods keep their platform default."""
    import jax
    plats = str(jax.config.jax_platforms
                or os.environ.get("JAX_PLATFORMS", ""))
    if "cpu" in plats.lower().split(","):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")


def pjrt_heartbeat_timeout_s() -> int:
    """The coordination-service liveness budget MV_Init hands to
    ``jax.distributed.initialize(heartbeat_timeout_seconds=...)``:
    ``-mv_pjrt_heartbeat_s``, or 600 in an ``-mv_elastic`` world that
    leaves the flag unset — a long-lived shrunk world must outlive the
    runtime's 100 s corpse detection. 0 = keep the runtime default."""
    secs = int(GetFlag("mv_pjrt_heartbeat_s"))
    if secs <= 0:
        try:
            elastic = bool(GetFlag("mv_elastic"))
        except KeyError:    # the elastic plane's module was never imported
            elastic = False
        secs = 600 if elastic else 0
    return secs


def _dist_initialize(**kw) -> None:
    import jax
    secs = pjrt_heartbeat_timeout_s()
    if secs:
        kw["heartbeat_timeout_seconds"] = secs
        Log.Info("multihost: PJRT coordination-service heartbeat timeout "
                 "%d s", secs)
    jax.distributed.initialize(**kw)


def maybe_initialize() -> bool:
    """Initialize jax.distributed per flags/env. Returns True when a
    multi-process runtime is (already or newly) up. Idempotent.

    Must run before anything initializes the XLA backend —
    ``jax.distributed.initialize()`` refuses once backends exist, so this
    function deliberately avoids jax calls (process_count etc.) on the
    decide-to-init path."""
    global _initialized, _owns_runtime
    mode = str(GetFlag("multihost")).lower()
    if mode == "off":
        return False
    coordinator = str(GetFlag("dist_coordinator"))
    rank = int(GetFlag("dist_rank"))
    size = int(GetFlag("dist_size"))
    explicit = bool(coordinator) and rank >= 0 and size > 0
    if not explicit and _net_world is not None:
        # MV_NetBind/MV_NetConnect wiring: rank 0's endpoint coordinates
        coordinator, rank, size = (_net_world[0], _net_rank,
                                   len(_net_world))
        explicit = True
    if _initialized:
        return True
    if not explicit and str(GetFlag("machine_file")):
        # reference ZMQ deployment: line N of the hosts file is rank N
        # (zmq_net.h ParseMachineFile); rank comes from -dist_rank or by
        # matching this host's addresses like the reference's net_util
        endpoints = _parse_machine_file(str(GetFlag("machine_file")))
        if endpoints:
            mf_rank = rank if rank >= 0 else _match_local_rank(endpoints)
            CHECK(mf_rank is not None and 0 <= mf_rank < len(endpoints),
                  f"-machine_file: cannot infer this process's rank (give "
                  f"-dist_rank); endpoints={endpoints}")
            coordinator, rank, size = endpoints[0], mf_rank, len(endpoints)
            explicit = True
    if not explicit and mode != "on" and not _env_says_multiprocess():
        return False
    if _initialized:
        return True
    import jax
    try:
        _enable_cpu_collectives()
        if explicit:
            _dist_initialize(coordinator_address=coordinator,
                             num_processes=size, process_id=rank)
        else:
            _dist_initialize()
        _initialized = True
        _owns_runtime = True
        Log.Info("multihost: jax.distributed up — process %d of %d",
                 jax.process_index(), jax.process_count())
        return True
    except Exception as exc:  # pragma: no cover - env-specific
        # "already initialized" / "must be called before any JAX
        # computations": a runtime may already be up (user or launcher
        # initialized first) — honor it when it is actually multi-process
        text = str(exc).lower()
        if "already" in text or "before" in text:
            if jax.process_count() > 1:
                _initialized = True
                return True
        CHECK(mode != "on" and not explicit,
              f"multihost requested but jax.distributed failed: {exc}")
        Log.Debug("multihost: auto-init skipped (%s)", exc)
        return False


def process_index() -> int:
    import jax
    return jax.process_index()


def process_count() -> int:
    import jax
    return jax.process_count()


def host_barrier(name: str = "mv_barrier") -> None:
    """Block until every member of the CURRENT world reaches this point
    (no-op single-member). Collective: every member must call it
    (reference controller barrier, controller.cpp:12-36)."""
    if _isolated:
        return
    if _group is not None:
        _group.barrier(name)
        return
    if process_count() <= 1:
        return
    from jax.experimental import multihost_utils
    note_collective()
    multihost_utils.sync_global_devices(name)


def host_allreduce_sum(data: np.ndarray) -> np.ndarray:
    """Elementwise sum of ``data`` across the current world's members
    (identity single-member). Collective."""
    if _isolated:
        return data
    if _group is not None:
        if _group.size <= 1:
            return data
        parts = host_allgather_objects(np.asarray(data))
        return np.sum(parts, axis=0).astype(data.dtype)
    if process_count() <= 1:
        return data
    from jax.experimental import multihost_utils
    note_collective()
    gathered = multihost_utils.process_allgather(data)  # (procs, *shape)
    return np.asarray(gathered).sum(axis=0).astype(data.dtype)


def host_allgather_bytes(data: bytes) -> list:
    """Every member's byte blob, ordered by world rank (collective;
    single-member: ``[data]``). Blobs may differ in length — lengths are
    exchanged first, then payloads ride one fixed-shape allgather padded
    to the global max (elastic groups ride the group transport in one
    keyed round instead)."""
    if _isolated:
        return [data]
    if _group is not None:
        return _group.exchange(data, "HAB")
    if process_count() <= 1:
        return [data]
    from jax.experimental import multihost_utils
    note_collective(2)   # length round + payload round
    lens = np.asarray(multihost_utils.process_allgather(
        np.array([len(data)], np.int64))).reshape(-1)
    cap = int(lens.max())
    if cap == 0:
        return [b""] * process_count()
    # quantize the padded capacity to the quarter-octave ladder
    # (mesh.next_bucket): process_allgather compiles per SHAPE, so
    # exact-max caps mint a fresh XLA program for every distinct payload
    # size; the ladder bounds the program set to ~4*log2(sizes) while
    # capping pad waste at ~25% — on the windowed engine's exchange the
    # padded bytes ARE the wire cost, and pow2 wasted up to 2x
    from multiverso_tpu.parallel.mesh import next_bucket
    cap = next_bucket(cap, min_bucket=1024)
    buf = np.zeros(cap, np.uint8)
    if data:
        buf[:len(data)] = np.frombuffer(data, np.uint8)
    gathered = np.asarray(
        multihost_utils.process_allgather(buf)).reshape(process_count(), cap)
    return [gathered[i, :int(lens[i])].tobytes()
            for i in range(process_count())]


def capped_exchange(blob: bytes, caps: dict, key, channel: int = 0) -> list:
    """Every process's byte blob in ONE collective round (steady state).

    The 2-round shape of host_allgather_bytes (lengths first, then the
    padded payload) pays two collective latencies per exchange — the
    dominant cost of small windows on the engine's windowed protocol.
    Here each exchange rides a STANDING per-``key`` capacity all ranks
    remember identically (``caps`` evolves only from exchanged data):
    blobs that fit inline in the cap'd buffer (1-byte fit flag + 8-byte
    little-endian length header — explicit ``'<i8'``, so heterogeneous-
    endianness worlds can't misread each other's lengths) complete in
    one round; if ANY rank overflowed, every rank runs one more round
    at the ladder cap of the now-known max length. After either path
    the standing cap snaps to the ladder rung of this exchange's max
    need, so per-key steady workloads (an engine window headed by the
    same verb) stay on the 1-round path. Collective; single-process
    returns ``[blob]``. In an elastic epoch the exchange rides the
    group transport instead (the gloo boot-world allgather cannot
    subset the world); ``caps`` are not consulted there — the relay is
    length-framed by construction.

    ``channel`` (round 12) selects an INDEPENDENT exchange stream on a
    transport that offers them (the shm wire: one per engine shard).
    The gloo path is one globally-ordered collective stream — callers
    must stay on channel 0 there (the engine clamps its shard count to
    the transport's channel count for exactly this reason)."""
    if _isolated:
        return [blob]
    if _group is not None:
        # the elastic group relay IS the collective: its whole wall is
        # blocked-in-collective time for the phase split
        _t0 = _time.perf_counter()
        out = _group.exchange(blob, key)
        _done = _time.perf_counter()
        _stamp_exchange(_t0, _done - _t0, _done, _time.time())
        return out
    if process_count() <= 1:
        return [blob]
    if _wire is not None:
        # installed wire (shm same-host / tcp cross-host): length-
        # framed by construction (caps unused); the whole call is the
        # collective for the phase split
        note_collective()
        _t0 = _time.perf_counter()
        out = _wire.exchange(blob, channel)
        _done_m, _done_w = _time.perf_counter(), _time.time()
        _stamp_exchange(_t0, _done_m - _t0, _done_m, _done_w)
        return out
    CHECK(channel == 0,
          "gloo host wire has ONE collective stream — channel "
          f"{channel} needs a multi-channel wire (-mv_wire=shm/tcp)")
    from jax.experimental import multihost_utils

    from multiverso_tpu.parallel.mesh import next_bucket
    _t0 = _time.perf_counter()   # after imports: first-call module-import
    need = len(blob) + 9         # cost must not be charged as exchange
    cap = caps.get(key, 4096)
    buf = np.zeros(cap, np.uint8)
    buf[0] = 1 if need <= cap else 0
    buf[1:9] = np.array([len(blob)], "<i8").view(np.uint8)
    if need <= cap and blob:
        buf[9:9 + len(blob)] = np.frombuffer(blob, np.uint8)
    note_collective()
    _tc = _time.perf_counter()
    gathered = np.asarray(
        multihost_utils.process_allgather(buf)).reshape(process_count(),
                                                        cap)
    _done_m, _done_w = _time.perf_counter(), _time.time()
    coll_s = _done_m - _tc
    lens = [int(np.frombuffer(gathered[i, 1:9].tobytes(), "<i8")[0])
            for i in range(process_count())]
    fits = [bool(gathered[i, 0]) for i in range(process_count())]
    caps[key] = next_bucket(max(lens) + 9, min_bucket=4096)
    if all(fits):
        _stamp_exchange(_t0, coll_s, _done_m, _done_w)
        return [gathered[i, 9:9 + lens[i]].tobytes()
                for i in range(process_count())]
    # overflow: one more round at the (now agreed) ladder cap
    big = caps[key]
    buf2 = np.zeros(big, np.uint8)
    if blob:
        buf2[: len(blob)] = np.frombuffer(blob, np.uint8)
    note_collective()
    _tc = _time.perf_counter()
    gathered2 = np.asarray(
        multihost_utils.process_allgather(buf2)).reshape(process_count(),
                                                         big)
    _done_m, _done_w = _time.perf_counter(), _time.time()
    coll_s += _done_m - _tc
    _stamp_exchange(_t0, coll_s, _done_m, _done_w)
    return [gathered2[i, : lens[i]].tobytes()
            for i in range(process_count())]


#: standing caps for host_allgather_objects(key=...) — lockstep callers
#: that tag their exchange get the capped 1-round path (caps evolve
#: identically everywhere because every tagged call site is collective)
_OBJ_CAPS: dict = {}


def host_allgather_objects_capped(obj, key) -> list:
    """host_allgather_objects through the standing-cap 1-round exchange
    (capped_exchange). ``key`` must be a value every rank passes
    identically at this lockstep call site — e.g. a call-site label —
    or buffer shapes diverge and the world hangs. Use for small,
    latency-sensitive agreements (the device planes' bucket rounds)."""
    if world_size() <= 1:
        return [obj]
    import pickle
    return [pickle.loads(b) for b in
            capped_exchange(pickle.dumps(obj), _OBJ_CAPS, key)]


def host_allgather_objects(obj) -> list:
    """Every member's picklable object, ordered by world rank
    (collective; single-member: ``[obj]``). Used by the table layer to
    merge per-process host-plane payloads — e.g. each process's row-id/delta
    batch of one logical Add — so reference PS semantics (every worker's
    Add accumulates, whichever process it ran on) hold across hosts."""
    if world_size() <= 1:
        return [obj]
    import pickle
    blobs = host_allgather_bytes(pickle.dumps(obj))
    return [pickle.loads(b) for b in blobs]


def merge_collective_add(option, *arrays, with_parts: bool = False):
    """Merge every process's payload of one collective row/key Add:
    allgathers ``(arrays..., option)``, CHECKs the option agrees on every
    process (divergent option scalars — worker_id, lr, momentum — would
    feed different jit'd updates into the same globally-sharded state and
    silently corrupt it), and returns per-position concatenations in
    process order. Identity single-process.

    ``with_parts``: also return the per-rank first arrays (the id sets),
    in rank order — SparseMatrixTable derives its per-keeper freshness
    transitions from them without a second host collective."""
    if world_size() <= 1:
        return (arrays, [arrays[0]]) if with_parts else arrays
    parts = host_allgather_objects((arrays, option))
    opts = [p[1] for p in parts]
    CHECK(all(o == opts[0] for o in opts),
          f"collective Add options diverge across processes: {opts}")
    merged = tuple(np.concatenate([p[0][i] for p in parts])
                   for i in range(len(arrays)))
    if with_parts:
        return merged, [p[0][0] for p in parts]
    return merged


def sum_collective_add(option, values: np.ndarray,
                       with_parts: bool = False):
    """Sum every process's delta of one collective whole-table Add (same
    option agreement CHECK as merge_collective_add). Identity
    single-process. ``with_parts``: also return the per-rank id sets —
    ``None`` per rank (a whole-table push)."""
    if world_size() <= 1:
        return (values, [None]) if with_parts else values
    parts = host_allgather_objects((values, option))
    opts = [p[1] for p in parts]
    CHECK(all(o == opts[0] for o in opts),
          f"collective Add options diverge across processes: {opts}")
    summed = np.sum([p[0] for p in parts], axis=0).astype(values.dtype)
    if with_parts:
        return summed, [None] * len(parts)
    return summed


def union_collective_ids(ids: np.ndarray) -> Optional[np.ndarray]:
    """Sorted union of every process's id/key set of one collective Get —
    the one identical set all processes gather so their device programs
    match. None single-process (caller keeps its local fast path)."""
    if world_size() <= 1:
        return None
    return np.unique(np.concatenate(host_allgather_objects(ids)))


def broadcast_from_master(data: np.ndarray) -> np.ndarray:
    """The world's lowest-rank member's value to everyone (identity
    single-member). Collective."""
    if _isolated:
        return data
    if _group is not None:
        if _group.size <= 1:
            return data
        return host_allgather_objects(np.asarray(data))[0]
    if process_count() <= 1:
        return data
    from jax.experimental import multihost_utils
    note_collective()
    return np.asarray(multihost_utils.broadcast_one_to_all(data))
