"""Zoo — the runtime singleton: mesh, roles, engine lifecycle, registries.

Behavioral equivalent of reference include/multiverso/zoo.h + src/zoo.cpp:
``Start`` parses flags, brings up the transport and actors in order, registers
the node, and barriers (zoo.cpp:41-103); ``Stop`` drains and shuts down
(zoo.cpp:104-113); it owns the actor registry, worker/server id maps, and the
barrier (zoo.cpp:116-177).

TPU mapping (see docs/DESIGN.md):

* The *server fabric* is the device mesh: ``num_servers`` = devices along the
  mesh ``server`` axis; shards live in HBM, so the reference's
  controller/communicator rank handshake (controller.cpp:38-77) reduces to
  mesh construction (+ ``jax.distributed`` across hosts).
* *Workers* are host execution streams: threads in one process (the
  reference's 1-process test world, multiverso_env.h) and processes across
  hosts. ``num_workers`` comes from the ``num_workers`` flag; each worker
  thread binds an id via ``worker_context``.
* One server *engine* actor serializes Get/Add application per the
  configured consistency mode (async / BSP sync — sync/server.py). In
  model-average mode (``-ma``) no engine starts, matching zoo.cpp:24,49;
  ``MV_Aggregate`` uses the rendezvous/psum allreduce instead.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from multiverso_tpu.message import Message, MsgType
from multiverso_tpu.node import ROLE_NAMES, Node, Role
# Imported for their flag registrations (sync, updater_type,
# telemetry/trace/stats_interval_s, mv_deadline_s/chaos_spec/chaos_seed)
# — they MUST be registered before
# Start()'s ParseCMDFlags runs, or a first-call "-sync=true" would be
# silently dropped.
import multiverso_tpu.elastic  # noqa: F401
import multiverso_tpu.failsafe  # noqa: F401
import multiverso_tpu.policy  # noqa: F401
import multiverso_tpu.replica  # noqa: F401
import multiverso_tpu.serving  # noqa: F401
import multiverso_tpu.sync.server  # noqa: F401
import multiverso_tpu.telemetry  # noqa: F401
import multiverso_tpu.updaters.base  # noqa: F401
from multiverso_tpu import elastic
from multiverso_tpu.failsafe import deadline as fdeadline
from multiverso_tpu.failsafe.errors import (ActorDied, DeadlineExceeded,
                                            MembershipChanged)
from multiverso_tpu.telemetry import metrics as tmetrics
from multiverso_tpu.telemetry import startup
from multiverso_tpu.parallel import multihost
from multiverso_tpu.parallel.allreduce import RendezvousAllreduce
from multiverso_tpu.parallel.mesh import MeshContext
from multiverso_tpu.utils.configure import (GetFlag, MV_DEFINE_bool,
                                            MV_DEFINE_int, MV_DEFINE_string,
                                            ParseCMDFlags)
from multiverso_tpu.utils.log import CHECK, Log
from multiverso_tpu.utils.waiter import Waiter

MV_DEFINE_string("ps_role", "default", "none / worker / server / default")
MV_DEFINE_bool("ma", False, "model-average mode: no parameter server")
MV_DEFINE_int("num_workers", 1, "number of in-process worker streams")

_thread_local = threading.local()


class Zoo:
    _instance: Optional["Zoo"] = None
    _instance_lock = threading.Lock()

    def __init__(self):
        self.started = False
        self.mesh_ctx: Optional[MeshContext] = None
        self.node = Node()
        self.num_workers = 1
        self.server_engine = None
        self.worker_tables: List[Any] = []
        self.server_tables: List[Any] = []
        self._barrier: Optional[threading.Barrier] = None
        self._allreduce: Optional[RendezvousAllreduce] = None
        self._ma_mode = False
        self._multihost = False

    # -- singleton ----------------------------------------------------------

    @classmethod
    def Get(cls) -> "Zoo":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = Zoo()
            return cls._instance

    # -- lifecycle (reference zoo.cpp:41-113) --------------------------------

    def Start(self, argv: Optional[List[str]] = None,
              devices=None) -> List[str]:
        CHECK(not self.started, "Zoo already started")
        # all of a start is the phase mv.init (gauge mv.init_s), with
        # mv.init.mesh and mv.init.planes inside it
        with startup.phase("mv.init"):
            return self._start(argv, devices)

    def _start(self, argv: Optional[List[str]], devices) -> List[str]:
        rest = ParseCMDFlags(argv or [])
        if not tmetrics.enabled():
            # the import's seconds and any compile before this line were
            # counted under the flag's default
            tmetrics.clear()
        startup.listen()
        self._ma_mode = bool(GetFlag("ma"))
        role = ROLE_NAMES.get(str(GetFlag("ps_role")).lower(), Role.ALL)
        self.num_workers = max(1, int(GetFlag("num_workers")))
        # multi-process bring-up BEFORE mesh construction: a multi-controller
        # job's mesh must span the global device set (SURVEY.md §2c — the
        # MPI/ZMQ transport's TPU equivalent is the cross-host mesh itself).
        # mv.init.mesh_s: where the backend answers in an app that had not
        # touched JAX
        with startup.phase("mv.init.mesh"):
            self._multihost = multihost.maybe_initialize()
            self.mesh_ctx = MeshContext.create(devices)
        if self._multihost:
            # host-wire selection BEFORE the engine exists (round 12):
            # same-host worlds ride the shared-memory wire, cross-host
            # worlds the framed tcp wire (round 24; -mv_wire) — either
            # wire's per-shard channels are what permit a sharded
            # engine's concurrent window streams in multi-process mode
            from multiverso_tpu.sync.server import \
                requested_engine_channels
            multihost.maybe_install_wire(requested_engine_channels())
        rank = multihost.process_index() if self._multihost else 0
        # stamp the trace-dump process label HERE, where the identity
        # is known on the app thread — dump callers (including the
        # replica serve loop) must never reach device work for it
        from multiverso_tpu.telemetry import trace as ttrace
        ttrace.set_process_label(f"multiverso rank {rank}")
        self.node = Node(rank=rank, role=role,
                         worker_id=0 if role & Role.WORKER else -1,
                         server_id=0 if role & Role.SERVER else -1)
        self._barrier = threading.Barrier(self.num_workers)
        # cross-host leg of MV_Aggregate: the rendezvous winner reduces the
        # thread-summed buffer across processes (reference MPI_Allreduce)
        cross = (multihost.host_allreduce_sum if self._multihost else None)
        self._allreduce = RendezvousAllreduce(self.num_workers,
                                              cross_reduce=cross)
        if not self._ma_mode:
            from multiverso_tpu.sync.server import Server
            self.server_engine = Server.GetServer(self.num_workers)
            self.server_engine.Start()
        # what the planes cost a start: gauge mv.init.planes_s
        with startup.phase("mv.init.planes"):
            from multiverso_tpu.telemetry.export import start_reporter
            start_reporter()        # -stats_interval_s periodic reports
            from multiverso_tpu.telemetry.ops import start_ops
            start_ops()             # -mv_ops_port /metrics·/healthz·/flight
            # watchdog plane (round 13): the byte ledger's mem.* gauges
            # register eagerly every world; the typed-rule tick thread only
            # arms when -mv_watchdog_s > 0 (off by default, like the
            # reporter). Both are LOCAL-only — no collectives ever.
            from multiverso_tpu.telemetry.accounting import start_ledger
            start_ledger()
            from multiverso_tpu.telemetry.watchdog import start_watchdog
            start_watchdog()
            # elastic membership plane LAST (needs the engine up): rank 0
            # hosts the coordinator, every rank registers + heartbeats
            elastic.start_plane(self)
            # replica fan-out AFTER elastic so its subscription registry
            # can ride the membership coordinator (round 17); rank 0 owns
            # the fan-out thread, every rank reads one cached flag
            from multiverso_tpu import replica as _replica
            _replica.start_plane(self)
            # policy plane LAST (round 20): it needs the watchdog's tick
            # listener hook and — multi-process — the elastic coordinator
            # endpoint (or its own -mv_policy_addr authority) already up
            from multiverso_tpu import policy as _policy
            _policy.start_plane(self)
        self.started = True
        Log.Debug("Zoo started: %d servers (mesh devices), %d workers, "
                  "mode=%s", self.num_servers, self.num_workers,
                  "ma" if self._ma_mode else
                  ("sync" if GetFlag("sync") else "async"))
        return rest

    def Stop(self, finalize_net: bool = True) -> None:
        if not self.started:
            return
        # ops plane down FIRST and BOUNDED: the HTTP daemon thread and
        # the periodic reporter are both joined through
        # failsafe.deadline.bounded paths, so back-to-back worlds in one
        # pytest process cannot leak daemon threads or find the ops port
        # still bound (-mv_ops_port=0 picks an ephemeral port per world
        # for exactly that reason)
        from multiverso_tpu.telemetry.export import stop_reporter
        stop_reporter()
        from multiverso_tpu.telemetry.ops import stop_ops
        stop_ops()
        # policy plane down BEFORE the watchdog that feeds it (no tick
        # may land on a dead engine) and before the engine it cuts
        from multiverso_tpu import policy as _policy
        _policy.shutdown_plane()
        # watchdog down with the other samplers and BOUNDED (its join
        # rides failsafe.deadline.bounded): a tick thread probing the
        # engine must not outlive it
        from multiverso_tpu.telemetry.watchdog import stop_watchdog
        stop_watchdog()
        from multiverso_tpu.telemetry.accounting import stop_ledger
        stop_ledger()
        if self.server_engine is not None:
            try:
                self.FinishTrain()
            except (DeadlineExceeded, ActorDied) as exc:
                # shutdown must LOG a stuck (or already-dead) engine and
                # keep tearing down (Actor.Stop below is itself bounded
                # and names a stuck actor + queue depth), never hang or
                # abandon the rest of the shutdown sequence
                Log.Error("Zoo.Stop: engine drain failed (%r) — "
                          "continuing shutdown", exc)
            self.server_engine.Stop()
            self.server_engine = None
        # the shm wire (when installed) outlives the engine — the
        # drain above still exchanged on it — and dies with the world
        multihost.close_wire()
        # replica fan-out down after the engine (no more publish cuts
        # can arrive) and BEFORE the elastic/serving planes it reads:
        # the fan-out thread stops, per-subscriber rings close, and any
        # hosted subscription coordinator dies with it — parked
        # replicas notice through their heartbeat failures
        from multiverso_tpu import replica as _replica
        _replica.shutdown_plane()
        # membership plane down AFTER the engine drain: the drain's
        # final flushes must still route under the CURRENT epoch view
        # (restoring the boot-world group earlier would aim the drain's
        # collectives at dead/departed boot peers). Heartbeats stop
        # here and the boot-world group is restored for the next
        # MV_Init.
        elastic.shutdown_plane()
        # serving plane down AFTER the engine (no more publishes can
        # arrive) — drops every snapshot and stops the dispatcher so a
        # later MV_Init world starts from a fresh plane
        from multiverso_tpu.serving import shutdown_plane
        shutdown_plane()
        # fleet fold last among the telemetry planes: everything that
        # pushed rollups into it (replica hb, elastic member hb, the
        # roster poll) is down, and the next world must start from an
        # EMPTY fleet — a surviving member would age into rollup_stale
        from multiverso_tpu.telemetry import fleet as _fleet
        _fleet.shutdown_plane()
        # one-flag postmortem: with -mv_diag_dir set, every world leaves
        # its flight ring + telemetry sidecar + span trace on disk at
        # teardown (failure paths already dumped the ring mid-flight)
        try:
            from multiverso_tpu.telemetry.ops import dump_diagnostics
            dump_diagnostics()
        except Exception as exc:   # diagnostics must never break Stop
            Log.Error("Zoo.Stop: diagnostics dump failed: %r", exc)
        self.worker_tables.clear()
        self.server_tables.clear()
        self.started = False
        Log.Debug("Zoo stopped")

    def FinishTrain(self) -> None:
        """Send Server_Finish_Train for every worker so a SyncServer drains
        its caches (reference zoo.cpp:152-162). Deadline-bounded when
        -mv_deadline_s is set: a wedged engine raises DeadlineExceeded
        (with the diagnostic bundle) instead of hanging the drain."""
        if self.server_engine is None:
            return
        self.flush_combined_adds()
        waiters = []
        for wid in range(self.num_workers):
            w = Waiter(1)
            msg = Message(msg_type=MsgType.Server_Finish_Train, src=wid,
                          waiter=w)
            self.server_engine.Receive(msg)
            waiters.append(w)
        for w in waiters:
            if not w.Wait(fdeadline.timeout_or_none()):
                fdeadline.raise_deadline("engine FinishTrain drain")

    # -- identity (reference zoo.h:40-66) ------------------------------------

    @property
    def rank(self) -> int:
        return self.node.rank

    @property
    def size(self) -> int:
        """Member count of the CURRENT world: the boot process count
        until an elastic epoch transition shrinks or regrows it."""
        return multihost.world_size() if self._multihost else 1

    @property
    def num_servers(self) -> int:
        if self._ma_mode or self.mesh_ctx is None:
            return 0 if self._ma_mode else 1
        return self.mesh_ctx.num_servers

    def current_worker_id(self) -> int:
        return getattr(_thread_local, "worker_id", 0)

    def worker_context(self, worker_id: int):
        """Bind the calling thread to a worker id (thread workers stand in
        for MPI rank workers — reference rank_to_worker_id maps)."""
        zoo = self

        class _Ctx:
            def __enter__(self):
                self._prev = getattr(_thread_local, "worker_id", None)
                CHECK(0 <= worker_id < zoo.num_workers,
                      f"worker_id {worker_id} out of range")
                _thread_local.worker_id = worker_id
                return zoo

            def __exit__(self, *exc):
                if self._prev is None:
                    del _thread_local.worker_id
                else:
                    _thread_local.worker_id = self._prev

        return _Ctx()

    def _id_to_member(self, global_id: int, per_member: int,
                      what: str) -> int:
        """Global worker/server id -> hosting member's boot rank under
        the CURRENT epoch view. Ids partition contiguously across the
        member list (member i hosts ids [i*per_member, (i+1)*per_member)
        — the boot-time mapping generalized to the live view). A stale
        id — one the current view no longer hosts because the world
        shrank — raises the TYPED MembershipChanged instead of
        returning a wrong rank (round 10 fix: these used to read the
        frozen boot mapping)."""
        CHECK(global_id >= 0, f"{what} id must be >= 0, got {global_id}")
        CHECK(per_member > 0, f"no {what}s in this world")
        view = (multihost.current_group().members
                if multihost.current_group() is not None
                else tuple(range(multihost.process_count()
                                 if self._multihost else 1)))
        member_pos = global_id // per_member
        if member_pos >= len(view):
            if elastic.enabled():
                raise MembershipChanged(
                    f"{what}_id_to_rank({global_id}) — the id maps past "
                    f"the current view", epoch=elastic.epoch(),
                    members=view)
            CHECK(False, f"{what} id {global_id} out of range for "
                         f"{len(view)} member(s) x {per_member}")
        return view[member_pos]

    def worker_id_to_rank(self, worker_id: int) -> int:
        return self._id_to_member(worker_id, self.num_workers, "worker")

    def server_id_to_rank(self, server_id: int) -> int:
        per = max(1, self.num_servers // max(1, self.size))
        return self._id_to_member(server_id, per, "server")

    # -- table registries (reference zoo.h:68-73) ---------------------------

    def RegisterServerTable(self, server_table) -> int:
        CHECK(self.server_engine is not None,
              "cannot create tables in -ma mode (reference zoo.cpp:49)")
        table_id = self.server_engine.RegisterTable(server_table)
        self.server_tables.append(server_table)
        return table_id

    def RegisterWorkerTable(self, worker_table) -> int:
        self.worker_tables.append(worker_table)
        return len(self.worker_tables) - 1

    def SendToServer(self, msg: Message) -> None:
        CHECK(self.server_engine is not None, "no server engine (ma mode?)")
        # a DEPARTED elastic member's verb fails typed instead of
        # forking the world's state (one bool read when the plane is off)
        elastic.guard_verbs()
        if msg.msg_type not in (MsgType.Request_Get, MsgType.Request_Add):
            # non-verb messages (StoreLoad, barrier pings, FinishTrain)
            # are ordering points: a checkpoint snapshot must include
            # every fire-and-forget Add issued before it, so the
            # combined-write buffers flush ahead of the message
            self.flush_combined_adds()
        self.server_engine.Receive(msg)

    def SendToServerMulti(self, members, tracked: bool = True) -> None:
        """Ship a batched verb submission (round 19, tables/base.py
        ``submit_multi``): the pre-built member messages ride ONE
        ``Request_MultiVerb`` envelope into the engine mailbox — one
        push, one window admission, one reply wake-up for the whole
        batch (the blocking path's measured ~3k verbs/s wall was the
        per-verb round trip, not the applies). A tracked batch is a
        global ordering point like any tracked verb: the combined-write
        buffers flush first so the batch's replies imply at least as
        much progress as the serial message stream would have shown.
        Engines that can't flatten envelopes (the BSP SyncServer counts
        Get/Add MESSAGES into its vector clocks — MULTI_VERB_OK False)
        receive the members individually instead: same stream order,
        just unbatched."""
        CHECK(self.server_engine is not None, "no server engine (ma mode?)")
        elastic.guard_verbs()
        if tracked:
            self.flush_combined_adds()
        eng = self.server_engine
        if not getattr(eng, "MULTI_VERB_OK", False):
            for m in members:
                eng.Receive(m)
            return
        eng.receive_multi(members)

    def CallOnEngine(self, msg_type: MsgType, fn, what: str,
                     timeout_s: Optional[float] = None):
        """Run ``fn()`` on the engine thread at the current stream
        position — the ONE consistent-cut mechanism (round 8): the
        engine treats any non-verb message as a window barrier, so every
        Add admitted before this call is applied first and none after,
        at a lockstep position in multi-process worlds. Checkpoint
        saves (Request_StoreLoad), serving publishes (Request_Publish)
        AND elastic membership transitions all ride this helper, so
        their cut semantics cannot drift. Bounded by ``timeout_s`` when
        given, else ``-mv_deadline_s``; engine-side failures re-raise
        here. (Elastic fences pass their own bound: a transition
        legitimately outlives a verb deadline — it blocks on a joiner's
        shard download.)"""
        CHECK(self.server_engine is not None,
              f"{what} needs a server engine (not -ma mode)")
        waiter = Waiter(1)
        msg = Message(msg_type=msg_type, payload={"fn": fn}, waiter=waiter)
        self.SendToServer(msg)   # flushes combined-write buffers first
        if not waiter.Wait(timeout_s if timeout_s is not None
                           else fdeadline.timeout_or_none()):
            fdeadline.raise_deadline(what, seconds=timeout_s)
        if isinstance(msg.result, Exception):
            raise msg.result
        return msg.result

    def flush_combined_adds(self) -> None:
        """Ship every table's combined-write buffer (round 7 worker-side
        write combining, tables/base.py). Called at every global
        ordering point — tracked verbs, barriers, engine drains,
        shutdown — so a buffered fire-and-forget Add can never be
        observed as missing where the serial message stream would have
        shown it. Cheap when nothing is buffered."""
        for t in self.worker_tables:
            flush = getattr(t, "FlushCombined", None)
            if flush is not None:
                flush()

    # -- collectives --------------------------------------------------------

    def DrainServer(self) -> None:
        """Round-trip a barrier ping through the engine mailbox: returns
        only after every previously-enqueued request — including
        fire-and-forget Adds — has been applied (native ServerC
        kRequestBarrier parity). No-op when no engine runs (-ma mode)."""
        if self.server_engine is None:
            return
        self.flush_combined_adds()
        waiter = Waiter(1)
        msg = Message(msg_type=MsgType.Request_Barrier, waiter=waiter)
        self.server_engine.Receive(msg)
        if not waiter.Wait(fdeadline.timeout_or_none()):
            fdeadline.raise_deadline("engine barrier ping (DrainServer)")
        if isinstance(msg.result, Exception):
            raise msg.result

    def _barrier_wait(self, leg: str) -> int:
        """One in-process barrier rendezvous, deadline-bounded: a worker
        thread that never arrives raises DeadlineExceeded (with the
        diagnostic bundle) on every waiting thread instead of blocking
        them forever. timeout=None (flag unset) blocks exactly as
        before."""
        timeout = fdeadline.timeout_or_none()
        try:
            return self._barrier.wait(timeout)
        except threading.BrokenBarrierError:
            # Barrier.wait(timeout) breaks the barrier for EVERY waiter
            # (and a peer's deadline/abort lands here too) — after a
            # divergence the barrier stays broken, which is the correct
            # fail-fast posture. Flag unset: propagate the raw
            # BrokenBarrierError exactly as before.
            if timeout is None:
                raise
            fdeadline.raise_deadline(f"worker barrier ({leg})")

    def Barrier(self) -> None:
        """Worker barrier (reference zoo.cpp:164-177 controller roundtrip):
        all in-process worker threads, then — multihost — all processes
        (one host_barrier per rendezvous, issued by every process
        collectively). With -mv_deadline_s set, a diverged rank (peer
        never reaches the barrier) raises DeadlineExceeded within the
        deadline instead of hanging in the collective."""
        CHECK(self._barrier is not None, "Zoo not started")
        if self.server_engine is not None:
            # combined-write flush BEFORE the rendezvous: after a
            # barrier every worker's earlier pushes must be in the
            # engine stream (the serial-message-stream contract)
            self.flush_combined_adds()
        _t0 = time.perf_counter()
        idx = self._barrier_wait("enter")
        if self._multihost:
            if idx == 0:
                try:
                    fdeadline.bounded(multihost.host_barrier,
                                      "cross-host barrier")
                except BaseException:
                    # release the peers loudly (BrokenBarrierError) instead
                    # of stranding them; a failed cross-host barrier means a
                    # peer process is gone — the job cannot proceed
                    self._barrier.abort()
                    raise
            self._barrier_wait("exit")  # hold threads until cross-host ends
        # telemetry: how long this thread sat in the barrier (straggler
        # skew shows up as a wide distribution here)
        tmetrics.histogram("zoo.barrier_wait_s").observe(
            time.perf_counter() - _t0)

    def Aggregate(self, data: np.ndarray) -> np.ndarray:
        """In-place elementwise-sum allreduce across workers
        (reference MV_Aggregate, src/multiverso.cpp:53-56)."""
        CHECK(self._allreduce is not None, "Zoo not started")
        result = self._allreduce.allreduce(data)
        np.copyto(data, result.astype(data.dtype))
        return data

    @classmethod
    def _reset_for_tests(cls) -> None:
        with cls._instance_lock:
            if cls._instance is not None and cls._instance.started:
                cls._instance.Stop()
            cls._instance = None
