"""Server engines: async (default) and BSP sync.

Behavioral equivalent of reference src/server.cpp:

* ``Server`` — async ASGD mode: applies every Get/Add as it arrives and
  always replies (server.cpp:23-58). Workers never wait for each other;
  the shard application itself is a jit'd XLA op dispatched asynchronously,
  so the actor thread stays ahead of the device.

* ``SyncServer`` — BSP mode (``-sync=true``): the exact vector-clock
  protocol of server.cpp:60-222, re-implemented: Adds from workers whose Get
  clock ran ahead of the global Get round are cached; Gets from workers with
  outstanding/uncounted Adds are cached; completing an Add round drains
  cached Gets and vice versa; ``Server_Finish_Train`` forces a worker's
  clocks to infinity and drains (server.cpp:188-211). Guarantee preserved
  (comment at server.cpp:60-67): all workers' i-th Get returns identical
  parameters, assuming all workers issue the same number of Gets/Adds.
  The clocks judge every message by itself, in mailbox order; the device
  work of what they admit is served through the same window as
  ``Server``'s, cut so that no Add passes a Get and no Get an Add
  (``SyncServer._cut``): consecutive admitted Adds of a table are one
  stretch, consecutive admitted Gets one dispatch-then-finalize in
  which Gets of the same rows share one gather.

Selection by the ``sync`` flag mirrors ``Server::GetServer``
(server.cpp:224-232).
"""

from __future__ import annotations

import collections
import functools
import threading
import time as _time
from typing import Deque, Dict, List, Optional

import numpy as np

from multiverso_tpu.actor import Actor, actor_names
from multiverso_tpu.failsafe import chaos
from multiverso_tpu.failsafe import deadline as fdeadline
from multiverso_tpu.failsafe.dedup import DedupWindow
from multiverso_tpu.failsafe.errors import (DeadlineExceeded,
                                            MembershipChanged,
                                            TransientError, WireCorruption)
from multiverso_tpu.message import Message, MsgType, copy_result
from multiverso_tpu.parallel import compress
from multiverso_tpu.parallel import multihost
from multiverso_tpu.parallel import wire
from multiverso_tpu.telemetry import flight as tflight
from multiverso_tpu.telemetry import metrics as tmetrics
from multiverso_tpu.telemetry import trace as ttrace
from multiverso_tpu.updaters.base import AddOption, GetOption
from multiverso_tpu.utils.configure import (GetFlag, MV_DEFINE_bool,
                                            MV_DEFINE_int, MV_DEFINE_string,
                                            cached_bool_flag,
                                            cached_int_flag,
                                            cached_str_flag)
from multiverso_tpu.utils.dashboard import monitor_region
from multiverso_tpu.utils.log import CHECK, Log
from multiverso_tpu.utils.mt_queue import MtQueue


MV_DEFINE_bool("sync", False, "sync or async")
# Windowed-engine transport selection (the reference picks its allreduce
# wire adaptively by payload size, allreduce_engine.cpp:31-55). "host":
# every window payload rides the staging allgather (capped_exchange).
# "device": eligible Add values never cross the host wire — only their
# dtype/shape metadata does — and the data moves through the table's
# device-parts collectives (place_parts + one traced program; on a pod
# that is ICI at fabric bandwidth). "auto": per-verb by payload size
# against -window_device_min_bytes. The default threshold sits just
# above a single-host crossover that is a CPU-backend measurement
# (gloo), not a chip number; see ROADMAP.md D2/S7: one host window round
# cost ~1.6 ms latency + bytes at ~350-410 MB/s, while one device-parts
# round cost a FIXED ~14-15 ms floor on the CPU backend — per-call jit
# dispatch + gloo collectives over padded parts buffers — so the device
# wire only won past ~4-6 MB per window, which a 4 MB-budget window
# barely reaches. No cell exchanges windows between processes, so the
# chip's crossover is not measured; a pod deployment, whose device wire
# is ICI, would want -window_transport=device or a lower threshold.
# each constant feeds both the flag registration and the cached
# accessor's fallback, so the two defaults cannot drift apart
_WINDOW_TRANSPORT_DEFAULT = "auto"
_WINDOW_DEVICE_MIN_BYTES_DEFAULT = 6 << 20
MV_DEFINE_string("window_transport", _WINDOW_TRANSPORT_DEFAULT,
                 "windowed-engine Add-value transport: auto / host / device")
MV_DEFINE_int("window_device_min_bytes", _WINDOW_DEVICE_MIN_BYTES_DEFAULT,
              "auto transport: defer Add values >= this many bytes to "
              "the device wire (default just above this host's measured "
              "crossover)")
# both are read per window on the pack path — listener-cached reads,
# not a registry RLock walk per window (hot-path-flag-cache law)
_window_transport_flag = cached_str_flag("window_transport",
                                         _WINDOW_TRANSPORT_DEFAULT)
_window_device_min_bytes_flag = cached_int_flag(
    "window_device_min_bytes", _WINDOW_DEVICE_MIN_BYTES_DEFAULT)
# Round 7 — PIPELINED window engine. The serial engine ran drain ->
# encode -> exchange -> apply strictly in sequence on the actor thread,
# parking every worker behind the whole chain. With the pipeline a
# dedicated EXCHANGE thread owns the host-wire collective stream
# (encode + capped_exchange + decode, strictly in SEQ order — the
# collective sequence every rank issues is unchanged) while the engine
# actor stays the APPLY stage: window N applies while window N+1
# exchanges, but ONLY when window N's apply is host-local on every rank
# (no device-wire positions and every touched table's
# mh_apply_is_local() — both decided from EXCHANGED bytes, so all ranks
# gate identically and an apply-side device collective can never race
# the exchange thread's allgather into a rank-divergent order).
# A Matrix / SparseMatrix table's apply is a device program on every
# backend, so the overlap serves KV tables alone (ROADMAP.md D5).
# -mv_pipeline=false restores the serial engine exactly.
MV_DEFINE_bool("mv_pipeline", True,
               "pipelined windowed engine: overlap window N's apply "
               "with window N+1's host exchange (false = serial engine)")
_pipeline_flag = cached_bool_flag("mv_pipeline", True)
# Round 12 — the three measured walls (PR 8 critpath: binding phase
# `apply` 22/47 windows, every fence `depth`, host_scaling flat because
# ONE actor serializes every table) attacked through one refactor:
# engine SHARDS (per-table-group actors, each with its own window
# stream / exchange stage / SEQ counter), a tunable pipeline DEPTH,
# and a parallel APPLY pool for different tables of one window.
MV_DEFINE_int("mv_engine_shards", 0,
              "engine shards: per-table-group engine actors, each "
              "owning its own window stream, exchange stage and SEQ "
              "counter; tables route by table_id %% shards (rank-"
              "agreed, no negotiation). 0 = auto: single-process "
              "worlds use min(tables, cores/4) via lazy shard spawn, "
              "multi-process worlds stay at 1 unless set explicitly "
              "(>1 there needs a multi-channel wire's per-shard "
              "channels — -mv_wire=shm same-host, tcp cross-host — "
              "because gloo is one globally-ordered "
              "collective stream). 1 = today's single engine byte-for-"
              "byte. Clamped to 1 under -sync (the BSP vector clocks "
              "count verbs across ALL tables) and -mv_elastic (the "
              "epoch relay is single-channel).")
MV_DEFINE_int("mv_pipeline_depth", 2,
              "pipelined engine depth cap: max exchanged-but-unapplied "
              "windows before the exchange stage fences (PR 6/8 "
              "measured every burst fence as `depth` — a transiently "
              "slow apply stops fencing the exchange at higher "
              "depths, at the cost of pinning more decoded windows)")
_pipeline_depth_flag = cached_int_flag("mv_pipeline_depth", 2)
MV_DEFINE_int("mv_apply_workers", 4,
              "apply-stage worker pool: apply DIFFERENT tables' "
              "segments of one exchanged window concurrently (per-"
              "table apply order stays serial, so determinism is "
              "untouched; only host-local windows parallelize — a "
              "collective apply keeps the strict position order). "
              "<=1 = serial apply, today's engine")
_apply_workers_flag = cached_int_flag("mv_apply_workers", 4)
# Worker-side fast paths (tables/base.py reads these through listener
# caches; they are DEFINED here so zoo's eager `import
# multiverso_tpu.sync.server` registers them before MV_Init's
# ParseCMDFlags — a flag defined in a lazily-imported module would
# silently drop its first-call CLI setting).
MV_DEFINE_int("mv_write_combine", 8,
              "worker-side write combining: coalesce up to N "
              "consecutive fire-and-forget Adds to one table into ONE "
              "request before the mailbox hop (0 = off, byte-identical "
              "message stream). A COUNT cap, deliberately not bytes: "
              "fire-and-forget call sequences are program-structural "
              "and therefore lockstep across SPMD ranks, while payload "
              "bytes can skew per rank — a byte cap would flush ranks "
              "at different call positions and diverge the multi-"
              "process verb streams.")
MV_DEFINE_int("mv_get_staleness", 0,
              "worker-side Get cache: serve a repeated identical Get "
              "from the last fetched result while the engine has "
              "applied at most N windows since the fill and this "
              "worker process wrote nothing to the table (SSP-style "
              "bounded staleness; 0 = off, every Get exact). "
              "Single-process worlds only — a cache hit removes a verb "
              "from the stream, which the multi-process SPMD collective "
              "contract cannot tolerate.")

# Round 11 — performance forensics. Every window's lifecycle is
# stamped per rank as compact flight events keyed by (mepoch, SEQ):
# form (verbs waiting for the stage to pick them up), pack, encode,
# exchange (with the time BLOCKED IN THE COLLECTIVE split out from
# local staging via multihost.last_exchange_stats — the exchange-done
# wall stamp is also the cross-rank clock-alignment rendezvous), decode
# and apply, with apply time additionally attributed per table family
# and verb kind. telemetry/critpath.py merges per-rank dumps into a
# cross-rank timeline and names the binding rank + phase per window.
# Rides the flight recorder's listener-cached gate; the tier-1 overhead
# guard (tests/test_critpath.py) holds the stamping to the same <=2%
# blocking-round budget as the recorder itself.
MV_DEFINE_bool("mv_phase_stamps", True,
               "per-window lifecycle phase stamping (form/pack/encode/"
               "exchange/decode/apply flight events + engine.phase.* "
               "histograms; false = window events only). No-op while "
               "-mv_flight_events=0 gates the recorder off. "
               "Multi-process windows stamp EVERY window (the "
               "cross-rank critical path needs every (mepoch, SEQ) "
               "position, and those windows cost a collective each); "
               "single-process windows observe the apply histogram "
               "every window but sample the flight events + per-table "
               "attribution 1-in-32 — those windows run in ~250us and "
               "per-window stamping would blow the 2% blocking-round "
               "budget the tier-1 guard enforces.")
_phase_stamps_flag = cached_bool_flag("mv_phase_stamps", True)

#: single-process sampling period for the full stamp (power of two;
#: window 1, 33, 65, ... stamp — the FIRST window always does, so
#: short tests and short jobs still leave phase records)
_PH_SP_SAMPLE = 32

#: the window lifecycle phase taxonomy (order = the gauge encoding of
#: engine.binding_phase: index into this tuple, -1 = none yet).
#: ``exchange_wait`` is the slice of ``exchange`` blocked inside the
#: collective op itself — the part a straggling peer inflates.
ENGINE_PHASES = ("form", "pack", "encode", "exchange", "exchange_wait",
                 "decode", "apply")

#: table families the per-family apply-seconds histograms are
#: registered for eagerly (visible at zero from the first scrape);
#: custom table classes get a lazy family from their class name
_TABLE_FAMILIES = ("matrix", "sparse", "array", "kv")


def _table_family(table) -> str:
    """Short family label of a server table for the apply attribution
    (``SparseMatrixServerTable`` -> ``sparse``, ``KVServerTable`` ->
    ``kv``; unknown classes degrade to their lowercased class name)."""
    name = type(table).__name__.lower()
    for fam in ("sparse", "kv", "array", "matrix"):
        if fam in name:
            return fam
    return name.replace("servertable", "").replace("table", "") or "table"


#: apply-stage poll granularity while an exchange is in flight: the
#: actor keeps draining the mailbox (feeding the NEXT window) between
#: polls instead of blocking inside the collective like the serial
#: engine did. One exchange costs >= the ~1.6ms allgather latency, so
#: 2ms polls add at most one spin per window.
_PL_POLL_S = 0.002

_INF = float("inf")

#: fence-cause taxonomy (round 9 — the observability plane's answer to
#: "overlap_pct sits at ~36%: WHAT fences?"). Every stall of the
#: pipelined exchange stage is classified into exactly one cause and
#: counted in ``engine.fence.<cause>``, with the stall seconds observed
#: into the ``engine.fence.stall_s`` histogram:
#:
#: * ``barrier``        — a non-verb window head (StoreLoad / Publish /
#:                        barrier ping / FinishTrain): its dispatch may
#:                        itself run collectives, so the stage fences
#:                        until the actor reports it done;
#: * ``nonlocal_table`` — a touched table's apply is not host-local
#:                        (mh_apply_is_local() False): the apply runs
#:                        device collectives that must not race the
#:                        exchange thread's allgather;
#: * ``device_wire``    — a window position's values rode the device
#:                        wire (DeferredArray): same collective-apply
#:                        reasoning;
#: * ``depth``          — the DEPTH cap: the apply stage simply hasn't
#:                        kept up (the only cause raising the cap or
#:                        speeding the apply would remove).
FENCE_CAUSES = ("barrier", "nonlocal_table", "device_wire", "depth")


class _ApplyPool:
    """Daemon-thread worker pool for the parallel apply
    (-mv_apply_workers). Deliberately NOT concurrent.futures: its
    worker threads are non-daemon and joined at interpreter exit, so
    one apply job wedged in a native call would turn a clean fatal
    shutdown into a process that never exits. These workers are
    daemons draining an MtQueue; jobs signal completion through a
    per-job box + event, and shutdown just closes the queue."""

    def __init__(self, workers: int, name: str):
        self._q: MtQueue = MtQueue()
        #: thread count this pool was built with — the adaptive-tuning
        #: path (round 20 policy plane) compares it against the live
        #: -mv_apply_workers value and rebuilds the pool between
        #: windows when they differ
        self.workers = max(1, workers)
        for i in range(self.workers):
            threading.Thread(target=self._loop, daemon=True,
                             name=f"mv-apply-{name}-{i}").start()

    def submit(self, fn) -> dict:
        box = {"done": threading.Event()}
        self._q.Push((fn, box))
        return box

    def _loop(self) -> None:
        ttrace.name_native_thread()
        while True:
            ok, item = self._q.Pop()
            if not ok:
                return
            fn, box = item
            try:
                box["result"] = fn()
            except BaseException as exc:    # re-raised by the waiter
                box["error"] = exc
            box["done"].set()

    def shutdown(self) -> None:
        self._q.Exit()


class _StageKilled(Exception):
    """Internal: the apply stage killed the exchange stage after a
    fatal engine error — exit quietly, the actor already failed every
    in-pipeline waiter."""


class VectorClock:
    """Per-worker progress clock (reference server.cpp:81-137).

    ``Update(i)`` ticks worker i; returns True when the tick completes a
    round (global clock catches up to the max local clock).
    """

    def __init__(self, n: int):
        self._local: List[float] = [0] * n
        self._global = 0

    def Update(self, i: int) -> bool:
        self._local[i] += 1
        if self._global < min(self._local):
            self._global += 1
            if self._global == self._max_element():
                return True
        return False

    def FinishTrain(self, i: int) -> bool:
        self._local[i] = _INF
        m = min(self._local)
        if self._global < m:
            self._global = m
            if self._global == self._max_element():
                return True
        return False

    def _max_element(self) -> float:
        finite = [v for v in self._local if v != _INF]
        return max([self._global] + finite)

    def local_clock(self, i: int) -> float:
        return self._local[i]

    def global_clock(self) -> float:
        return self._global

    def staleness(self) -> float:
        """How far the fastest still-training worker runs ahead of the
        global round — the BSP skew the telemetry gauge tracks (0 when
        every worker is caught up or finished)."""
        finite = [v for v in self._local if v != _INF]
        return max(max(finite) - self._global, 0.0) if finite else 0.0

    def DebugString(self) -> str:
        local = " ".join("-1" if v == _INF else str(int(v)) for v in self._local)
        return f"global {self._global} local: {local}"


class _ExchangeStage:
    """EXCHANGE stage of the pipelined windowed engine (round 7).

    One daemon thread owns the host-wire collective stream: every window
    exchange and barrier head-marker exchange runs here, strictly in
    stream order, so the collective sequence each rank issues is
    identical to the serial engine's however the apply stage is
    scheduled. Items flow actor -> ``_in`` -> this thread -> ``out`` ->
    actor:

    * ``("verbs", [msgs])`` — admitted Get/Add messages, appended to the
      stage's pending deque. The thread packs pending into windows
      (byte budget + transport deferral), exchanges each, agrees on the
      cross-rank prefix, and emits ``("window", mine, windows, prefix,
      descs0, t0)``; verbs beyond the agreed prefix stay pending and
      lead the next exchange (the serial engine's re-led-window rule).
    * ``("barrier", msg)`` — a non-verb window head: the thread flushes
      every pending verb first (stream order), runs the head-marker
      exchange, and emits ``("barrier", msg)`` for the actor to
      dispatch in order.
    * ``("stop", None)`` — thread exit (engine shutdown).

    OVERLAP GATE: after emitting a window whose apply is NOT host-local
    (any device-wire position, or a table without mh_apply_is_local())
    — and after every barrier, whose dispatch may itself run
    collectives — the thread FENCES: no further collective until the
    actor reports that item applied. The gate decision derives only
    from exchanged bytes and rank-agreed table state, so every rank
    fences at the same windows and apply-side device collectives never
    interleave with exchange-thread allgathers in rank-divergent order.

    Failsafe: the collective itself stays deadline-bounded
    (fdeadline.bounded inside _mh_exchange_decode); a fence that never
    lifts (apply stage wedged) raises DeadlineExceeded under
    -mv_deadline_s. ANY escape parks the stage (``dead``) and emits
    ``("error", exc)`` — the actor fails every in-pipeline waiter and
    poisons itself, exactly the serial engine's fatal contract.
    """

    def __init__(self, srv: "Server"):
        self._srv = srv
        #: max exchanged-but-not-yet-applied items (-mv_pipeline_depth,
        #: default 2): bounds how far the exchange runs ahead (decoded
        #: windows pin their blobs in memory). Round 20: read through
        #: the listener cache at EVERY gate, not once per stage life —
        #: the policy plane tunes the flag live, and the cap is pacing
        #: only (window CONTENT stays the exchanged/agreed prefix), so
        #: ranks reading different values for a window or two cannot
        #: diverge the stream; they just fence at different depths.
        self.depth_cap = max(1, _pipeline_depth_flag())
        self._in: MtQueue = MtQueue()
        self.out: MtQueue = MtQueue()
        self._pending: Deque[Message] = collections.deque()
        self._emitted = 0
        self._applied = 0
        self._fence_at = 0
        #: why _fence_at was last raised (fence-cause profiling); the
        #: depth-cap stall is classified separately in _gate
        self._fence_cause = "barrier"
        self._cv = threading.Condition()
        self._killed = False
        self.dead: Optional[BaseException] = None
        #: overlap telemetry: wall-clock start of the in-flight exchange
        #: (0.0 = idle) + total busy seconds; the apply stage intersects
        #: its intervals against these (see Server._note_overlap)
        self.busy_since = 0.0
        self.busy_s = 0.0
        #: perf forensics: when the CURRENT pending run started filling
        #: (0.0 = empty) — the window's "form" phase is the stretch its
        #: verbs waited for the stage to pick them up
        self._pending_since = 0.0
        # the WORLD rank (elastic membership view), not the boot rank:
        # exchanged windows index by position in the current member
        # order. A stage never survives an epoch transition (the rebase
        # retires it), so binding at construction is sound.
        self._my_rank = multihost.world_rank()
        self._thread = threading.Thread(target=self._main,
                                        name="mv-engine-exchange",
                                        daemon=True)
        self._thread.start()

    # -- actor-side API -----------------------------------------------------

    def feed_verbs(self, msgs: List[Message]) -> None:
        self._in.Push(("verbs", msgs))

    def feed_barrier(self, msg: Message) -> None:
        self._in.Push(("barrier", msg))

    def note_applied(self) -> None:
        """The actor finished processing one emitted item — lifts the
        depth bound and any fence waiting on it."""
        with self._cv:
            self._applied += 1
            self._cv.notify_all()

    def stop(self) -> None:
        self._in.Push(("stop", None))
        self._in.Exit()

    def poison(self) -> None:
        """Apply-stage kill switch after a fatal engine error: a stage
        left with pending verbs must issue NO further collectives (the
        stream is desynced) and must not block shutdown on a fence the
        dead actor will never lift."""
        self._killed = True
        with self._cv:
            self._cv.notify_all()
        self._in.Exit()

    def depth(self) -> int:
        """Exchanged-but-unapplied items (diagnostics)."""
        return self._emitted - self._applied

    def pending_verbs(self) -> int:
        return len(self._pending)

    # -- stage thread -------------------------------------------------------

    def _wait_applied(self, upto: int, what: str) -> None:
        timeout = fdeadline.timeout_or_none()
        with self._cv:
            ok = self._cv.wait_for(
                lambda: self._applied >= upto or self._killed, timeout)
        if self._killed:
            raise _StageKilled()
        if not ok:
            fdeadline.raise_deadline(what, fatal=True)

    _GATE_WHAT = "pipelined engine apply fence (apply stage did not drain)"

    def _gate(self) -> None:
        """Before ANY new collective: honour the fence (a non-local
        apply or barrier dispatch may be running device collectives on
        the actor thread) and the pipeline depth bound.

        Fence-cause profiling (round 9): when the gate actually stalls,
        the stall is classified (the explicit fence's recorded cause,
        or ``depth`` when only the DEPTH cap holds it) and its seconds
        observed — this is the dataset behind raising overlap_pct."""
        # live depth (round 20): one cached-dict read per gate, so a
        # policy-plane -mv_pipeline_depth update takes effect at the
        # NEXT window instead of never
        self.depth_cap = max(1, _pipeline_depth_flag())
        depth_target = self._emitted - self.depth_cap + 1
        target = max(self._fence_at, depth_target)
        # advisory read (GIL-atomic int): only classifies; correctness
        # stays with the cv wait below
        if self._applied >= target:
            self._wait_applied(target, self._GATE_WHAT)
            return
        cause = (self._fence_cause if self._fence_at >= depth_target
                 else "depth")
        t0 = _time.perf_counter()
        self._wait_applied(target, self._GATE_WHAT)
        self._srv._note_fence(cause, _time.perf_counter() - t0)

    def _main(self) -> None:
        try:
            self._loop()
        except _StageKilled as exc:
            # actor-side kill: every waiter was already failed there —
            # park dead WITHOUT emitting an error item
            self.dead = self.dead or exc
        except BaseException as exc:  # delivered to the apply stage
            self.dead = exc
            self.out.Push(("error", exc))

    def _loop(self) -> None:
        items: Deque = collections.deque()
        while not self._killed:
            # absorb everything already queued (larger windows, and a
            # barrier behind queued verbs is seen before we block)
            while True:
                ok, it = self._in.TryPop()
                if not ok:
                    break
                items.append(it)
            if not items and not self._pending:
                ok, it = self._in.Pop()     # idle: block for work
                if not ok:
                    return
                items.append(it)
                continue
            # input order is admission order: only LEADING verb items
            # may join pending ahead of a queued barrier
            while items and items[0][0] == "verbs":
                if not self._pending:
                    self._pending_since = _time.perf_counter()
                self._pending.extend(items.popleft()[1])
            if self._pending:
                self._exchange_one()
                continue
            kind, payload = items.popleft()
            if kind == "stop":
                return
            # barrier head: marker exchange at this stream position;
            # its dispatch (actor side) may run collectives, so fence
            # until the actor reports it done
            self._gate()
            self._srv._mh_check_barrier_head(payload)
            self._emitted += 1
            self._fence_at = self._emitted
            self._fence_cause = "barrier"
            self.out.Push(("barrier", payload))

    def _exchange_one(self) -> None:
        srv = self._srv
        self._gate()
        verbs = list(self._pending)
        t0 = _time.perf_counter()
        self.busy_since = t0
        # perf forensics: the window's phase record, threaded through
        # the exchange (this thread) into the apply stage (the actor),
        # emitted as ONE compact flight event at apply-done
        ph = None
        if srv._phases_on():
            ph = {}
            if self._pending_since:
                ph["form"] = max(0.0, t0 - self._pending_since)
        try:
            # the "server.window" span opens HERE (parented to the head
            # verb, exactly like the serial engine) so the nested
            # exchange span stays its child and the apply stage parents
            # its apply span to it — one tree per window across both
            # stage threads
            with ttrace.span("server.window", cat="server",
                             parent=verbs[0].trace_ctx,
                             args={"pending": len(verbs)}) as win_ctx:
                _tp = _time.perf_counter()
                local, used = srv._mh_pack_window(verbs)
                if ph is not None:
                    ph["pack"] = _time.perf_counter() - _tp
                windows = srv._mh_exchange_decode(local, self._my_rank,
                                                  ph)
        finally:
            now = _time.perf_counter()
            self.busy_since = 0.0
            self.busy_s += now - t0
            a0 = srv._apply_since
            if a0:
                # this exchange ended while an apply was running: the
                # overlapped stretch is ours to record (the apply-side
                # intersection only sees exchanges still in flight)
                srv._note_overlap(max(0.0, now - max(a0, t0)))
        prefix = min(len(w) for w in windows)
        descs = [[(k, t) for k, t, _ in w[:prefix]] for w in windows]
        srv._flight_exchanged(descs, self._my_rank)
        CHECK(all(d == descs[0] for d in descs),
              f"multi-process verb streams diverge inside a window: "
              f"{descs} — every process must issue the same table-verb "
              f"sequence (the SPMD collective contract)")
        for _ in range(prefix):
            self._pending.popleft()
        self._emitted += 1
        # re-led verbs' form clock restarts HERE: form measures how
        # long the next window's head waited since the stage could
        # have started it (the previous window's cut), not since the
        # verb's original arrival — a stalled run would otherwise read
        # cumulative, unbounded form times
        self._pending_since = (_time.perf_counter() if self._pending
                               else 0.0)
        fence_cause = srv._mh_fence_cause(descs[0], windows, prefix)
        if fence_cause is not None:
            self._fence_at = self._emitted
            self._fence_cause = fence_cause
        if ph is not None:
            ph["seq"] = srv._mh_seq - 1
            ph["mepoch"] = multihost.membership_epoch()
        self.out.Push(("window", used[:prefix], windows, prefix, descs[0],
                       t0, win_ctx, ph, fence_cause))


#: the two kinds of stretch a window's verbs are cut into
#: (``Server._cut``): one table's Adds applied as ONE run, and Gets
#: dispatched one behind the other and finalized together
_ADDS, _GETS = "adds", "gets"


def _extend_stretch(out: list, kind: str, msg: Message) -> None:
    """``msg`` joins the stretch that ends ``out`` if that is one of its
    kind (and, for Adds, of its table); a new stretch begins otherwise."""
    last = out[-1] if out else None
    if (isinstance(last, tuple) and last[0] is kind
            and (kind is _GETS or last[1][0].table_id == msg.table_id)):
        last[1].append(msg)
    else:
        out.append((kind, [msg]))


class Server(Actor):
    """Async server engine (reference server.cpp:23-58)."""

    def __init__(self, name: str = actor_names.kServer):
        super().__init__(name)
        self.store_: List = []  # ServerTable list (reference server.h:24)
        #: round 12 — sharded engine: which wire channel this engine's
        #: window stream exchanges on, and the matching flight-event
        #: stream id ((mepoch, stream, SEQ) keying). 0 for the
        #: unsharded engine and shard 0; sub-shards override both.
        self.mh_channel = 0
        self.mh_stream = 0
        #: lazy apply-stage worker pool (-mv_apply_workers)
        self._apply_pool = None
        #: True while this engine is the ONLY window stream issuing
        #: collectives in a multi-process world (a ShardedServer with
        #: live sub-shards sets False on every shard: collective
        #: applies then CHECK-fail loudly — see _mh_fence_cause)
        self.mh_single_collective_stream = True
        #: windows split by a non-Get/Add barrier message (observability +
        #: lets tests assert the barrier path actually engaged)
        self.window_barrier_splits = 0
        #: multi-process windowed protocol observability: verbs processed
        #: through collective windows / window exchanges issued
        self.mh_window_verbs = 0
        self.mh_window_exchanges = 0
        #: ... and the Add-application economics the burst tests assert:
        #: dispatches actually issued (merged run = 1), runs that merged
        #: across positions AND ranks, and positions whose values rode
        #: the DEVICE wire (transport selection; see -window_transport)
        self.mh_add_dispatches = 0
        self.mh_add_run_merged = 0
        self.mh_device_wire_adds = 0
        #: standing exchange capacities per window-head descriptor
        #: (multihost.capped_exchange) — evolves identically on every
        #: rank, keeping steady exchanges to ONE collective round
        self._mh_caps: Dict = {}
        #: failsafe: window-exchange sequence stamp. Incremented only on
        #: a SUCCESSFUL exchange, so every rank's counter marches in
        #: lockstep; a rank that re-enters the exchange alone (after an
        #: asymmetric CRC failure) pairs with its peers' NEXT round and
        #: the seq mismatch CHECK fires loudly on every rank instead of
        #: silently merging different windows
        self._mh_seq = 0
        # telemetry (telemetry/metrics.py; NULL instruments when off).
        # The mh_* int attributes above stay — tests assert them — and
        # the typed instruments mirror them into snapshots/exports.
        self._t_window_s = tmetrics.histogram("server.window.latency_s")
        self._t_encode_s = tmetrics.histogram("server.wire.encode_s")
        self._t_decode_s = tmetrics.histogram("server.wire.decode_s")
        self._t_exchanges = tmetrics.counter("server.window.exchanges")
        self._t_verbs = tmetrics.counter("server.window.verbs")
        self._t_splits = tmetrics.counter("server.window.barrier_splits")
        self._t_dispatch = tmetrics.counter("server.add.dispatches")
        self._t_merged = tmetrics.counter("server.add.run_merged")
        #: Gets answered from an identical Get's gather and copy back
        self._t_shared = tmetrics.counter("server.get.shared")
        self._t_defer = tmetrics.counter("server.add.device_deferrals")
        #: host-vs-device transport byte accounting: what this rank
        #: actually shipped on the host staging wire vs what it kept
        #: local for the device-parts collectives (DeferredArray)
        self._t_host_bytes = tmetrics.counter("server.wire.host_bytes")
        self._t_dev_bytes = tmetrics.counter("server.wire.device_bytes")
        self._t_budget = tmetrics.gauge("server.window.host_budget_bytes")
        #: failsafe: (src, msg_id) at-most-once window for Adds + its
        #: hit counter (worker retries / duplicate deliveries answered
        #: from the record instead of re-applying)
        try:
            dedup_cap = int(GetFlag("mv_dedup_window"))
        except Exception:
            dedup_cap = 4096
        self._dedup = DedupWindow(dedup_cap)
        self._t_dedup_hits = tmetrics.counter("failsafe.dedup_hits")
        # registered eagerly (not on first increment) so a healthy run's
        # MV_MetricsSnapshot() shows the failsafe machinery at ZERO —
        # dashboards can alert on these without probing for existence
        tmetrics.counter("failsafe.deadline_exceeded")
        tmetrics.counter("failsafe.retries")
        tmetrics.counter("wire.crc_failures")
        # round 7 — pipelined engine + worker-side fast paths:
        #: windows applied by THIS engine (every topology) — the
        #: worker-side staleness-bounded Get cache's epoch source
        #: (tables/base.py; a plain int: GIL-atomic reads from workers)
        self.window_epoch = 0
        #: exchange/apply overlap telemetry: percentage of exchange-
        #: stage busy seconds that ran concurrently with an apply
        self._t_overlap_pct = tmetrics.gauge("engine.overlap_pct")
        tmetrics.counter("worker.write_combine_hits")   # eager (see above)
        tmetrics.counter("worker.get_cache_hits")
        # round 9 — fence-cause profiling: every pipelined-stage stall
        # classified (FENCE_CAUSES above) + its seconds. Registered
        # eagerly so the -stats_interval_s reporter and /metrics show
        # the whole breakdown at zero from the first scrape — the
        # dataset the ROADMAP's overlap attack reads.
        for _cause in FENCE_CAUSES:
            tmetrics.counter(f"engine.fence.{_cause}")
        self._t_fence_stall_s = tmetrics.histogram("engine.fence.stall_s")
        #: last classified fence cause (dashboard [Ops] line probe)
        self.last_fence_cause = ""
        # round 11 — perf forensics: phase histograms + per-family
        # apply seconds + the local binding-phase gauge, all registered
        # EAGERLY so /metrics and the -stats_interval_s reporter show
        # the whole taxonomy at zero from the first scrape
        # handles CACHED on the engine (not looked up per window: the
        # registry get takes a lock + an f-string — measurable against
        # the <=2% phase-stamp budget on the blocking round)
        self._t_phase = {p: tmetrics.histogram(f"engine.phase.{p}_s")
                         for p in ENGINE_PHASES}
        #: round 22 fleet digest: whole-window seconds (phase totals),
        #: merged across ranks via the heartbeat rollups so /fleet can
        #: quote a fleet-wide window p99. Handle cached like _t_phase —
        #: a per-window registry get would bill the 2% budget.
        self._d_window = tmetrics.digest("digest.engine.window_s")
        self._t_apply_fam = {
            fam: tmetrics.histogram(f"engine.apply.table_s.{fam}")
            for fam in _TABLE_FAMILIES}
        #: tid -> (family, histogram) cache for the apply attribution
        self._fam_cache: Dict[int, tuple] = {}
        #: locally-dominant lifecycle phase of the last stamped window,
        #: encoded as its ENGINE_PHASES index (-1 = none yet). A LOCAL
        #: proxy only — the cross-rank binding verdict needs every
        #: rank's dump (telemetry/critpath.py); the handler serving
        #: this stays never-collective.
        self._t_binding = tmetrics.gauge("engine.binding_phase")
        self._t_binding.set(-1.0)
        self.last_binding_phase = ""
        #: round 13 — watchdog plane saturation surfaces. apply_busy_s
        #: accumulates this STREAM's total apply seconds as a plain
        #: float (one add per window — the watchdog/ops refresh mirrors
        #: it into the engine.shard<k>.* gauges off the hot path; a
        #: per-window gauge.set would bill its lock against the 2%
        #: blocking-round budget). xw_busy_s accumulates seconds
        #: blocked inside the window-exchange collective the same way.
        #: Both are UNCONDITIONAL — the watchdog's straggler rule reads
        #: them so it keeps working with ``-mv_phase_stamps=0`` or the
        #: flight recorder off. The per-stream binding gauge is
        #: resolved lazily: sub-shards learn their stream id AFTER
        #: construction.
        self.apply_busy_s = 0.0
        self.xw_busy_s = 0.0
        #: round 20 — policy-plane routing inputs, accumulated
        #: UNCONDITIONALLY on the actor thread (plain dict int/float
        #: adds; apply-pool jobs return private dicts that merge here,
        #: so only the engine-shard domain ever writes these):
        #: per-table verbs this stream processed, and per-table apply
        #: seconds (multi-process windows). The shard_imbalance ->
        #: routing-map decider picks the hottest table of the hottest
        #: stream from exactly these tallies (rebalance.plan_routing).
        self.table_verbs: Dict[int, int] = {}
        self.table_apply_s: Dict[int, float] = {}
        self._t_binding_st = None
        self._t_pool_jobs = tmetrics.counter("engine.apply_pool.jobs")
        self._t_pool_inline = tmetrics.counter(
            "engine.apply_pool.inline_jobs")
        #: single-process window counter for the 1-in-N full-stamp
        #: sampling + the current window's stamp decision (read by
        #: _local_window for the per-table attribution gating)
        self._ph_tick = 0
        self._ph_stamp_this = False
        self._ex_stage: Optional[_ExchangeStage] = None
        self._apply_since = 0.0   # apply interval start (overlap calc)
        self._overlap_s = 0.0
        self._overlap_lock = threading.Lock()
        self.RegisterHandler(MsgType.Request_Get, self._get_entry)
        self.RegisterHandler(MsgType.Request_Add, self._add_entry)
        # round 19 — batched verb envelopes flatten into the window at
        # drain time (_expand_multi), so the window entry handles them;
        # counters registered eagerly (the PR 6 scrape-at-zero rule)
        self.RegisterHandler(MsgType.Request_MultiVerb, self._get_entry)
        self._t_multi = tmetrics.counter("engine.multi_verb_batches")
        self._t_multi_size = tmetrics.histogram("engine.multi_verb_size")
        self.RegisterHandler(MsgType.Server_Finish_Train, self.ProcessFinishTrain)
        # barrier ping: replies once the mailbox drained up to this point —
        # must NOT touch the BSP clocks, unlike FinishTrain (native
        # ServerC registers the same handler, native/src/store.cc)
        self.RegisterHandler(MsgType.Request_Barrier, lambda m: m.reply(None))
        # table persistence on the engine thread: the snapshot/restore in
        # payload["fn"] cannot race applied Adds (native kStoreTable/
        # kLoadTable parity, native/src/store.cc HandleStoreLoad)
        self.RegisterHandler(MsgType.Request_StoreLoad, self._store_load_entry)
        # serving-plane snapshot publish (round 8, serving/snapshot.py):
        # a non-verb message, so the window machinery above makes it a
        # BARRIER — windows split around it and the multi-process
        # head-marker exchange proves every rank dispatches it at the
        # same stream position. payload["fn"] captures every table at
        # that position: the consistent cut costs nothing beyond the
        # ordering the engine already enforces. SAME handler as
        # StoreLoad on purpose: checkpoint saves and publishes are one
        # cut mechanism (Zoo.CallOnEngine), so they cannot drift.
        self.RegisterHandler(MsgType.Request_Publish,
                             self._store_load_entry)

    #: worker-side fast paths gate on the engine's consistency mode:
    #: the async engine's contract (a Get may observe more progress,
    #: never less) admits both; the BSP SyncServer counts Get/Add
    #: MESSAGES into its vector clocks, so combining N Adds into one
    #: message (or serving a Get without a message) would desync the
    #: round accounting — SyncServer overrides both to False.
    GET_CACHE_OK = True
    WRITE_COMBINE_OK = True
    #: round 19 — whether the zoo may batch verbs into Request_MultiVerb
    #: envelopes for this engine. The async window engine takes them
    #: (members become ordinary window verbs); under the BSP SyncServer,
    #: whose clocks count messages, Zoo.SendToServerMulti falls back to
    #: delivering the members individually (same stream order).
    MULTI_VERB_OK = True

    def receive_multi(self, members) -> None:
        """Accept one batched verb submission: wrap the pre-built
        member messages in a Request_MultiVerb envelope and push it —
        ONE mailbox hop for the whole batch. The envelope's on_reply
        forwards a failure reply (actor death sweep / handler error on
        the envelope itself) to every member, so batch waiters raise
        typed instead of hanging when the engine dies mid-flight."""
        env = Message(msg_type=MsgType.Request_MultiVerb,
                      payload={"members": list(members)},
                      on_reply=_fail_multi_members)
        # straight to the mailbox (poison check + push): routing
        # already happened — ShardedServer.receive_multi split the
        # batch per shard before delegating here, and going back
        # through its Receive override would re-split forever
        Actor.Receive(self, env)

    def _expand_multi(self, batch: list) -> list:
        """Flatten Request_MultiVerb envelopes into their member verbs
        IN PLACE of the envelope's drain position — the members enter
        the window in submission order, ahead of anything drained after
        the envelope, which is exactly the serial-stream order N single
        submits would have produced. Members carry no mailbox enqueue
        stamp, so note_dequeue skips them (the envelope's one stamp
        already accounted the hop)."""
        out: list = []
        for m in batch:
            if m.msg_type is MsgType.Request_MultiVerb:
                self.note_dequeue(m)
                members = m.payload["members"]
                self._t_multi.inc()
                self._t_multi_size.observe(len(members))
                out.extend(members)
            else:
                out.append(m)
        return out

    def RegisterTable(self, server_table) -> int:
        table_id = len(self.store_)
        self.store_.append(server_table)
        # the id on the table itself: the perf-forensics surfaces
        # (apply attribution, row-skew sketch metrics) name tables by
        # family+id without walking the store
        server_table.table_id = table_id
        # replica plane (round 17): attach the publish dirty journal at
        # registration so the first post-publish interval is covered
        # from the table's birth (a late-attached journal costs one
        # full-payload fan-out). One cached-flag read when off.
        from multiverso_tpu import replica as _replica
        _replica.maybe_attach_journal(server_table)
        return table_id

    def Stop(self) -> None:
        if self._ex_stage is not None:
            self._ex_stage.stop()
        pool, self._apply_pool = self._apply_pool, None
        if pool is not None:
            # no join: the actor drain above already applied every
            # window, and the workers are daemons — a wedged job can
            # never hold the interpreter's exit hostage
            pool.shutdown()
        super().Stop()

    # -- round 12: sharded-engine facade points (the unsharded engine
    # IS shard 0 of a 1-shard world; ShardedServer overrides these) ----------

    def epoch_for_table(self, table_id: int) -> int:
        """Window epoch of the stream applying ``table_id``'s verbs —
        the worker-side Get cache's staleness clock (tables/base.py).
        Per-shard in a sharded engine: a busy NEIGHBOR shard must not
        age another table's cache entries."""
        return self.window_epoch

    def cut_epoch(self) -> int:
        """Total windows applied across every stream — the stream
        position a cross-stream cut (snapshot/checkpoint) is taken at
        (serving/snapshot.py stamps it into the published version)."""
        return self.window_epoch

    def shard_states(self) -> List[dict]:
        """Per-shard live state for /healthz and the dashboard
        [Engine] line (LOCAL, never collective)."""
        st = self._ex_stage
        return [{
            "shard": self.mh_stream,
            "actor": self.name,
            "poisoned": repr(self._poison) if self._poison is not None
            else None,
            "mailbox_depth": self.mailbox.Size(),
            "window_epoch": self.window_epoch,
            "window_exchanges": self.mh_window_exchanges,
            "apply_busy_s": round(self.apply_busy_s, 6),
            "xw_busy_s": round(self.xw_busy_s, 6),
            "window_verbs": self.mh_window_verbs,
            # snapshot copies: the watchdog/policy samplers hold these
            # across ticks while the actor keeps mutating the originals
            "table_verbs": dict(self.table_verbs),
            "table_apply_s": {t: round(v, 6)
                              for t, v in self.table_apply_s.items()},
            "stage": None if st is None else {
                "depth": st.depth(),
                "pending_verbs": st.pending_verbs(),
                "mid_exchange": bool(st.busy_since),
                "dead": repr(st.dead) if st.dead is not None else None,
            },
        }]

    def _flight_exchanged(self, descs, my_rank: int) -> None:
        """Flight event for one completed exchange: THIS rank's verbs
        over the AGREED prefix, recorded BEFORE the cross-rank
        divergence CHECK — so a diverging window is in the ring when
        the CHECK aborts it, which is what forensics.correlate aligns.
        The prefix (not the full local pack) is deliberate: ragged
        drains legally pack different window LENGTHS per rank, and a
        full-pack descriptor would read as a false divergence on a
        healthy stream."""
        if tflight.enabled():
            tflight.record("window.exchanged", seq=self._mh_seq - 1,
                           epoch=self.window_epoch,
                           mepoch=multihost.membership_epoch(),
                           stream=self.mh_stream,
                           detail=",".join(f"{k}{t}"
                                           for k, t in descs[my_rank]))

    def _note_fence(self, cause: str, stall_s: float) -> None:
        """Account one pipelined-stage stall: ``engine.fence.<cause>``
        counter + the stall-seconds histogram + a flight event. Called
        from the exchange stage thread only."""
        tmetrics.counter(f"engine.fence.{cause}").inc()
        self._t_fence_stall_s.observe(stall_s)
        self.last_fence_cause = cause
        tflight.record("fence", seq=self._mh_seq,
                       epoch=self.window_epoch,
                       mepoch=multihost.membership_epoch(),
                       stream=self.mh_stream, detail=cause)

    def _note_overlap(self, s: float) -> None:
        """Record ``s`` seconds of exchange/apply concurrency (called by
        whichever stage's interval closed while the other was active)
        and refresh the engine.overlap_pct gauge."""
        if s <= 0:
            return
        st = self._ex_stage
        with self._overlap_lock:
            self._overlap_s += s
            busy = st.busy_s if st is not None else 0.0
            if busy > 0:
                self._t_overlap_pct.set(
                    min(100.0, 100.0 * self._overlap_s / busy))

    # -- perf forensics: phase stamping (round 11) --------------------------

    def _phases_on(self) -> bool:
        """The phase-stamping gate: two cached flag reads (the flight
        recorder's listener-cached capacity + -mv_phase_stamps)."""
        return _phase_stamps_flag() and tflight.enabled()

    def _binding_stream_gauge(self):
        """The PER-STREAM binding-phase gauge (round 13 — the global
        ``engine.binding_phase`` is one name, so N shard streams would
        overwrite each other's verdicts). Lazy: a sub-shard's stream id
        is assigned after construction. Only touched when the binding
        phase CHANGES, so the lookup amortizes to nothing."""
        g = self._t_binding_st
        if g is None:
            g = self._t_binding_st = tmetrics.gauge(
                f"engine.stream{self.mh_stream}.binding_phase")
        return g

    def _ph_emit(self, ph: dict, nverbs: int) -> None:
        """Emit one window's phase record: the ``window.phases`` flight
        event (keyed by (mepoch, SEQ); durations in integer
        microseconds) + the engine.phase.*_s histograms + the local
        binding-phase gauge. Offsets in the detail re-anchor the
        window's monotonic landmarks to the event's OWN ``tm`` stamp:

        * ``xd`` — microseconds from exchange-done back to the event's
          ``tm`` (so exchange-done's wall time = the event's ``t`` -
          xd/1e6, which is the cross-rank rendezvous critpath aligns
          clocks on);
        * ``ax`` — microseconds from exchange-done to apply-start (the
          decode + depth-queue gap).

        Single-process windows carry only ``a`` (there is no exchange);
        their seq stays -1, which keeps them out of the cross-rank
        stream alignment by construction — and they take the fast path
        below, because they ARE the blocking hot loop the tier-1
        overhead guard times."""
        apply_s = ph.get("apply", 0.0)
        if "x" not in ph:
            # apply-only window: one observe + one flight record (the
            # gauge only moves when the binding phase CHANGES)
            if apply_s > 0.0:
                self._t_phase["apply"].observe(apply_s)
                self._d_window.observe(apply_s)
                if self.last_binding_phase != "apply":
                    self.last_binding_phase = "apply"
                    self._t_binding.set(
                        float(ENGINE_PHASES.index("apply")))
                    self._binding_stream_gauge().set(
                        float(ENGINE_PHASES.index("apply")))
            tflight.record("window.phases", seq=ph.get("seq", -1),
                           epoch=self.window_epoch,
                           mepoch=ph.get("mepoch", 0),
                           stream=self.mh_stream,
                           detail=f"v={nverbs};a={int(apply_s * 1e6)}")
            return
        durs = {"form": ph.get("form", 0.0), "pack": ph.get("pack", 0.0),
                "encode": ph.get("encode", 0.0),
                "exchange": ph.get("x", 0.0),
                "exchange_wait": ph.get("xw", 0.0),
                "decode": ph.get("dec", 0.0),
                "apply": ph.get("apply", 0.0)}
        for name, secs in durs.items():
            if secs > 0.0:
                self._t_phase[name].observe(secs)
        # window total for the fleet digest: exchange already contains
        # its wait portion, so the wait is not added again
        self._d_window.observe(sum(durs.values()) - durs["exchange_wait"])
        # local binding proxy: the phase that dominated this window's
        # wall locally (exchange_wait stands in for "a peer bound us")
        cand = {k: v for k, v in durs.items() if k != "exchange"}
        binding = max(cand, key=cand.get) if any(cand.values()) else ""
        if binding and binding != self.last_binding_phase:
            self.last_binding_phase = binding
            self._t_binding.set(float(ENGINE_PHASES.index(binding)))
            self._binding_stream_gauge().set(
                float(ENGINE_PHASES.index(binding)))
        parts = [f"v={nverbs}"]
        for tag, key in (("f", "form"), ("p", "pack"), ("e", "encode"),
                         ("x", "exchange"), ("xw", "exchange_wait"),
                         ("d", "decode"), ("a", "apply")):
            if durs[key] > 0.0:
                parts.append(f"{tag}={int(durs[key] * 1e6)}")
        x_done_m = ph.get("x_done_m", 0.0)
        if x_done_m:
            # anchor offsets vs a mono stamp taken JUST before record()
            # samples its own (the gap is the record call itself, ~us —
            # inside the documented alignment error bound)
            now_m = _time.perf_counter()
            parts.append(f"xd={int((now_m - x_done_m) * 1e6)}")
            a_start = ph.get("a_start_m", 0.0)
            if a_start:
                parts.append(f"ax={int((a_start - x_done_m) * 1e6)}")
        tflight.record("window.phases", seq=ph.get("seq", -1),
                       epoch=self.window_epoch,
                       mepoch=ph.get("mepoch", 0),
                       stream=self.mh_stream,
                       detail=";".join(parts))

    def _ph_tables(self, tbl: dict, seq: int, mepoch: int) -> None:
        """Apply-time attribution per (table, verb): one
        ``window.tables`` flight event (``<family><tid>:<A|G>=<us>``)
        + the per-family engine.apply.table_s.* histograms — the
        dataset that names WHICH table's ProcessAddRun is the
        depth-fence culprit."""
        parts = []
        items = (tbl.items() if len(tbl) == 1 else sorted(tbl.items()))
        for (tid, verb), secs in items:
            cached = self._fam_cache.get(tid)
            if cached is None:
                try:
                    fam = _table_family(self.store_[tid])
                except Exception:
                    fam = "table"
                hist = self._t_apply_fam.get(
                    fam) or tmetrics.histogram(
                        f"engine.apply.table_s.{fam}")
                cached = self._fam_cache[tid] = (fam, hist)
            fam, hist = cached
            hist.observe(secs)
            parts.append(f"{fam}{tid}:{verb}={int(secs * 1e6)}")
        if parts:
            tflight.record("window.tables", seq=seq,
                           epoch=self.window_epoch, mepoch=mepoch,
                           stream=self.mh_stream,
                           detail=";".join(parts))

    # -- elastic plane hooks (round 10, elastic/) ---------------------------

    def _elastic_rebase(self, mepoch: int, cause: str) -> None:
        """Epoch transition, ON the engine thread with the stream
        fenced: re-base the exchange stream for the new world — SEQ
        back to 0 (every surviving member re-bases at the same cut, so
        the counters stay lockstep), standing caps dropped (the world
        size changed, so per-key exchanged buffer shapes changed), and
        the exchange stage retired (the next window builds a fresh one
        bound to the new world rank)."""
        st = self._ex_stage
        if st is not None:
            st.poison()
            st.dead = st.dead or _StageKilled()
            self._ex_stage = None
        self._mh_seq = 0
        self._mh_caps.clear()
        tflight.record("membership.epoch", seq=0,
                       epoch=self.window_epoch, mepoch=mepoch,
                       detail=f"cause={cause}")
        Log.Info("engine: exchange stream re-based for membership "
                 "epoch %d (%s)", mepoch, cause)

    def _elastic_post_transition(self, pending) -> bool:
        """After a barrier dispatch that performed an epoch transition:
        when the new world is single-member the collective protocol is
        gone — drain the remaining pipeline/batch contents through the
        local window path and report True."""
        if multihost.world_size() > 1:
            return False
        batch = list(pending)
        pending.clear()
        if batch:
            self._local_window(batch)
            self.window_epoch += 1
            tflight.record("window.applied", epoch=self.window_epoch,
                           mepoch=multihost.membership_epoch(),
                           stream=self.mh_stream,
                           detail=f"{len(batch)}v")
        return True

    @staticmethod
    def _bounded_collective(fn, what: str):
        """fdeadline.bounded + membership-lease consult: a deadline on
        a collective asks the elastic authority whether a peer's lease
        expired BEFORE going fatal — a dead peer converts the deadline
        into the typed MembershipChanged the transition path handles
        (heartbeat leases riding the failsafe deadline machinery). No
        elastic plane (or every lease fresh): the DeadlineExceeded
        propagates exactly as before."""
        try:
            return fdeadline.bounded(fn, what)
        except MembershipChanged:
            raise
        except BaseException as exc:
            # a dead peer surfaces either as the deadline OR as a
            # transport error from the abandoned collective — both
            # consult the lease. Fresh leases: the original error
            # re-raises untouched (genuine divergence stays fatal).
            from multiverso_tpu import elastic
            repl = elastic.peer_loss(what) if elastic.enabled() else None
            if repl is not None:
                raise repl from exc
            raise

    #: how many queued messages one Get/Add drains into its window.
    #: Each pipelined Get hides one device->host copy RTT, queued Adds to
    #: one table coalesce into one merged dispatch, and identical queued
    #:  Gets share one gather; the window stays modest so other messages
    #: are not starved for long.
    GET_PIPELINE_WINDOW = 16

    def _admit(self, msg: Message) -> bool:
        """Failsafe admission gate, applied to every drained message
        BEFORE it can enter a window's verb stream.

        (1) At-most-once Adds: the (src, msg_id) dedup window answers a
        duplicate — a mailbox dup or a worker retry after a failed ack —
        from the recorded outcome instead of re-applying, and keeps it
        OUT of the SPMD verb stream, where an extra verb on one rank
        would trip the cross-rank divergence CHECK.

        (2) Chaos rehearsal: the armed injector may reject a tracked
        verb with TransientError before applying (driving the worker
        retry path) or mark an Add to apply-then-fail-its-ack (driving
        the retry INTO the dedup window). Decisions are consulted for
        every verb in admission order, so two SPMD ranks with the same
        seed fault the same lockstep positions."""
        if (msg.msg_type in (MsgType.Request_Add, MsgType.Request_Get)
                and getattr(msg, "_fs_admitted", False)):
            # duplicate delivery of the SAME object (a mailbox dup):
            # the admitted copy owns the reply — drop silently. Object
            # identity needs no window slot, so this holds for
            # fire-and-forget Adds too — and it covers Gets, whose
            # duplicate would double-tick the BSP get clock and desync
            # the SyncServer's round accounting.
            self._t_dedup_hits.inc()
            tflight.record("dedup.hit", epoch=self.window_epoch,
                           detail=f"obj src{msg.src}")
            return False
        if msg.msg_type is MsgType.Request_Add and msg.msg_id:
            key = (msg.src, msg.msg_id)
            tracked = msg.waiter is not None
            if tracked and self._dedup.seen(key):
                self._t_dedup_hits.inc()
                tflight.record("dedup.hit", epoch=self.window_epoch,
                               detail=f"retry src{msg.src}")
                ready, outcome = self._dedup.outcome(key)
                msg.reply(outcome if ready else TransientError(
                    "duplicate Add while the original is in flight"))
                return False
            failack = False
            cz = chaos.get()
            if cz is not None:
                action = cz.verb_action(tracked=tracked)
                if action == "transient":
                    msg.reply(TransientError("chaos: transient verb "
                                             "fault (pre-apply)"))
                    return False
                failack = action == "failack"
            msg._fs_admitted = True
            if tracked:
                # only TRACKED Adds occupy dedup slots: they are the
                # only ones a worker can retry, and a high-rate
                # fire-and-forget burst must not evict a pending retry
                # record (that eviction would break at-most-once)
                self._dedup.record(key)
                self._fs_wrap_reply(msg, key, failack)
            return True
        if msg.msg_type is MsgType.Request_Get:
            cz = chaos.get()
            if (cz is not None
                    and cz.verb_action(tracked=msg.waiter is not None)
                    == "transient"):
                # Gets only take the pre-serve transient fault — they
                # are idempotent (retry re-serves), so failack has
                # nothing to rehearse (the draw still advances, keeping
                # schedules lockstep across ranks)
                msg.reply(TransientError("chaos: transient verb fault"))
                return False
            msg._fs_admitted = True
        return True

    def _fs_wrap_reply(self, msg: Message, key, failack: bool) -> None:
        """Shadow ``msg.reply`` so the apply outcome lands in the dedup
        window the moment it is known (whichever engine path replies),
        and — chaos failack — the ACK delivered to the worker is
        corrupted into a TransientError while the recorded outcome stays
        truthful: the retry must be answered from the record, not
        re-applied."""
        orig = msg.reply
        dedup = self._dedup

        def _reply(result=None):
            dedup.set_outcome(key, result)
            if failack and not isinstance(result, Exception):
                orig(TransientError("chaos: ack failed after apply"))
            else:
                orig(result)

        msg.reply = _reply

    def _get_entry(self, msg: Message) -> None:
        """Window handler for Request_Get AND Request_Add, async engine.

        Drains a window of already-queued messages, then:

        * ADD COALESCING — all Adds to one table inside the window apply
          as ONE merged dispatch (table.ProcessAddRun) at the position of
          the table's FIRST Add. Later Adds of the run thereby land
          before any Get queued between them — legal under the async
          contract (a Get may observe MORE progress, never less: every
          coalesced Add was already enqueued when the Get was). Falls
          back to per-message ProcessAdd when the table declines the
          merge (aux updaters, multihost, validation doubts). Any
          OTHER message type (StoreLoad, flag sets, ...) is a window
          BARRIER: runs split at it, so an Add acknowledged before a
          Load is never re-applied after the restore.
        * GET DEDUP — identical queued Gets (same table, payload,
          option) share one device gather; extra repliers get copies.
        * GET PIPELINING — distinct Gets overlap their device->host
          copies (dispatch all, finalize after), as before.

        SyncServer takes its window the same way and serves it through
        the same ``_local_window``; what it overrides is ``_cut``, how
        a window's verbs are cut into stretches: under BSP no Add may
        pass a Get and no Get an Add."""
        batch = self._take_window(msg, self.GET_PIPELINE_WINDOW)
        if not batch:
            return
        if multihost.world_size() > 1:
            # multi-process WINDOWED protocol (round 5): one host
            # collective exchanges the whole window; verbs then apply
            # from the exchanged parts with cross-rank coalescing/dedup.
            self._mh_windows(batch)
            return
        self._run_window(batch)

    def _take_window(self, msg: Optional[Message], cap: int) -> list:
        """``msg`` (None: nothing in hand) and what the mailbox holds
        behind it now, envelopes flattened: those the failsafe gate
        admits, in mailbox order. ``cap`` messages at most."""
        with ttrace.span("server.window.admit", cat="server"):
            batch = [] if msg is None else [msg]
            while len(batch) < cap:
                ok, nxt = self.mailbox.TryPop()
                if not ok:
                    break
                batch.append(nxt)
            # round 19 — batched verb envelopes flatten here, BEFORE
            # admission/windowing: each member is an ordinary stream verb
            # from this point on (dedup slots, chaos draws, window
            # positions, replies), so one envelope = one admission but N
            # lockstep stream positions
            batch = self._expand_multi(batch)
            for m in batch:
                # drained members bypass _dispatch — observe their queue
                # wait here (idempotent; the head was noted there
                # already, and multi members carry no enqueue stamp)
                self.note_dequeue(m)
            # failsafe admission (dedup + chaos) BEFORE windowing: a
            # duplicate or chaos-rejected verb must never become a stream
            # position (divergent descriptors across ranks otherwise)
            return [m for m in batch if self._admit(m)]

    def _take_late(self, taken: int) -> list:
        """What a window that has served ``taken`` messages and not yet
        copied its Gets back takes on top: nothing here (SyncServer
        overrides)."""
        return []

    def _run_window(self, batch) -> None:
        """One single-process window, with its span and instruments
        (``batch`` grows by what the window took late)."""
        _t0 = _time.perf_counter()
        phases = self._phases_on()
        if phases:
            self._ph_tick += 1
            self._ph_stamp_this = (self._ph_tick
                                   & (_PH_SP_SAMPLE - 1)) == 1
        else:
            self._ph_stamp_this = False
        with ttrace.span("server.window", cat="server",
                         args=({"verbs": len(batch)}
                               if ttrace.enabled() else None)):
            self._local_window(batch)
        self.window_epoch += 1     # worker get-cache staleness clock
        tflight.record("window.applied", epoch=self.window_epoch,
                       stream=self.mh_stream,
                       detail=f"{len(batch)}v")
        _win_s = _time.perf_counter() - _t0
        self._t_window_s.observe(_win_s)
        # a single-process window's whole body IS apply — the per-shard
        # load number the watchdog's imbalance rule compares (one plain
        # float add: within the blocking-round overhead budget)
        self.apply_busy_s += _win_s
        if phases:
            # single-process window: the whole body is apply (there is
            # no exchange); seq stays -1 so these never enter the
            # cross-rank stream alignment. The apply histogram sees
            # EVERY window; the flight record rides the 1-in-N sample
            # (see the -mv_phase_stamps help text)
            if self._ph_stamp_this:
                self._ph_emit({"apply": _win_s}, len(batch))
            else:
                self._t_phase["apply"].observe(_win_s)
        # count Add/Get verbs only, like the mh path's prefix count —
        # the counter must mean the same thing in every topology
        self._t_verbs.inc(sum(1 for m in batch if m.msg_type in
                              (MsgType.Request_Add, MsgType.Request_Get)))

    def _local_window(self, batch) -> None:
        """Serve one drained single-process window (see _get_entry): the
        one window loop of both engines. Any non-Get/Add message (e.g.
        Request_StoreLoad's Load, FinishTrain) mutates state outside the
        Add/Get algebra: it BARRIERS the window, runs through its own
        handler at its position, and nothing is formed, merged or
        shared across it. The verbs between two barriers are cut into
        stretches by the class (``_cut``) and served in that order
        (``_serve``); every dispatched Get is copied back and answered
        when all of the window has been dispatched (``_finalize``). A
        segment is cut only when everything before it has run: what a
        barrier's handler changes (the BSP clocks, at FinishTrain) is
        seen by the cut behind it. Before the copies back the class may
        take what has landed since (``_take_late``): it is appended to
        ``batch`` and served as the window's next messages."""
        pending: list = []   # (finalize, [msgs]) in dispatch order
        seen: Dict[tuple, int] = {}
        # perf forensics: per-(table, verb) apply seconds — only on the
        # 1-in-N sampled windows (_run_window decides; the elastic
        # post-transition drain path leaves the flag wherever the last
        # window set it, which is fine for a sampled surface)
        tbl = {} if self._ph_stamp_this else None
        at = 0
        while at < len(batch):
            segments: list = [[]]
            for m in batch[at:]:
                if m.msg_type in (MsgType.Request_Add, MsgType.Request_Get):
                    segments[-1].append(m)
                else:
                    segments += [m, []]         # barrier marker
            at = len(batch)
            for seg in segments:
                if not isinstance(seg, list):
                    # barrier: runs its normal handler in order, with
                    # standard error routing; no dedup survives it
                    self.window_barrier_splits += 1
                    self._t_splits.inc()
                    tflight.record("barrier", epoch=self.window_epoch,
                                   stream=self.mh_stream,
                                   detail=MsgType(seg.msg_type).name)
                    self._dispatch(seg)
                    seen.clear()
                    continue
                if not seg:
                    continue
                with ttrace.span("server.window.form", cat="server"):
                    for m in seg:
                        # round 20 — policy routing input (actor thread
                        # only)
                        if m.table_id >= 0:
                            self.table_verbs[m.table_id] = (
                                self.table_verbs.get(m.table_id, 0) + 1)
                    stretches, share = self._cut(seg)
                self._serve(stretches, share, pending, seen, tbl)
            batch += self._take_late(len(batch))
        self._finalize(pending, tbl)
        if tbl:
            self._ph_tables(tbl, -1, 0)

    def _cut(self, verbs):
        """The Adds and Gets between two barriers, cut into the
        stretches ``_serve`` runs in order: ``(_ADDS, msgs)``, one
        table's Adds as ONE run, and ``(_GETS, msgs)``, Gets dispatched
        one behind the other. -> (stretches, whether the Gets are keyed
        for sharing a gather: a key costs tobytes of the payload arrays,
        so only where the verbs could hold a duplicate). The
        asynchronous cut: all of a table's Adds at the position of its
        FIRST (see _get_entry)."""
        add_runs: Dict[int, list] = {}
        for m in verbs:
            if m.msg_type is MsgType.Request_Add:
                add_runs.setdefault(m.table_id, []).append(m)
        n_gets = len(verbs) - sum(map(len, add_runs.values()))
        out: list = []
        for m in verbs:
            if m.msg_type is MsgType.Request_Get:
                _extend_stretch(out, _GETS, m)
            elif m.table_id in add_runs:
                out.append((_ADDS, add_runs.pop(m.table_id)))
        return out, n_gets > 1

    def _serve(self, stretches, share: bool, pending: list, seen: dict,
               tbl) -> None:
        """Run a cut's stretches in order. An Adds stretch is one
        ``_process_add_run``. A Get is dispatched (``ProcessGetAsync``)
        and joins ``pending`` for the caller's ``_finalize``; with
        ``share``, one whose request equals that of a Get dispatched
        since the table's last Add (``seen``) rides that Get's gather
        and copy back. A table without a two-phase Get answers on the
        spot. A callable (the BSP cut's: a drain) runs at its position and
        dispatches into the same ``pending`` and ``seen``."""
        for st in stretches:
            if callable(st):
                st(pending, seen)
                continue
            kind, msgs = st
            if kind is _ADDS:
                tid = msgs[0].table_id
                _tt = _time.perf_counter() if tbl is not None else 0.0
                self._process_add_run(msgs)
                if tbl is not None:
                    k = (tid, "A")
                    tbl[k] = tbl.get(k, 0.0) + _time.perf_counter() - _tt
                # a Get queued after this Add must not join a gather
                # dispatched before it (it would observe LESS progress
                # than was enqueued ahead of it) — drop the table's
                # dedup entries
                for k in [k for k in seen if k[0] == tid]:
                    del seen[k]
                continue
            for m in msgs:
                key = self._get_dedup_key(m) if share else None
                if key is not None and key in seen:
                    pending[seen[key]][1].append(m)
                    self._t_shared.inc()
                    continue
                _tt = _time.perf_counter() if tbl is not None else 0.0
                with monitor_region("SERVER_PROCESS_GET"):
                    try:
                        table = self.store_[m.table_id]
                        finalize = table.ProcessGetAsync(**m.payload)
                        if finalize is None:
                            self.ProcessGet(m)
                        else:
                            if key is not None:
                                seen[key] = len(pending)
                            pending.append((finalize, [m]))
                    except Exception as exc:
                        # failures (bad table id included) reply to
                        # THIS message only — an escape here would
                        # abandon every pending finalize and hang
                        # their waiters
                        Log.Error("table ProcessGet dispatch failed: "
                                  "%r", exc)
                        m.reply(exc)
                if tbl is not None:
                    k = (m.table_id, "G")
                    tbl[k] = tbl.get(k, 0.0) + _time.perf_counter() - _tt

    def _finalize(self, pending, tbl) -> None:
        """The blocking device->host fetch of each dispatched Get, and
        the replies."""
        with ttrace.span("server.window.finalize", cat="server"):
            for finalize, msgs in pending:
                _tt = _time.perf_counter() if tbl is not None else 0.0
                err = None
                try:
                    result = finalize()
                except Exception as exc:
                    Log.Error("table %d Get finalize failed: %r",
                              msgs[0].table_id, exc)
                    err = exc
                if tbl is not None:
                    k = (msgs[0].table_id, "G")
                    tbl[k] = tbl.get(k, 0.0) + _time.perf_counter() - _tt
                if err is not None:
                    for m in msgs:
                        m.reply(err)
                    continue
                msgs[0].reply(result)
                for m in msgs[1:]:
                    # each deduped caller owns its result arrays: a copy
                    # of the copy before it, not of the first answer,
                    # which is a view of the buffer the device wrote and
                    # reads at a fraction of the host's own memory
                    # (1.7 ms against 0.2 ms for 2 MB on a v5e's host:
                    # PERF.md section 6, PR 51)
                    result = copy_result(result)
                    m.reply(result)

    # -- multi-process WINDOWED protocol (round 5) --------------------------
    # The r4 design took the strict path: every table verb ran its own
    # host collective (allgather merge), forfeiting windows, coalescing
    # and dedup in any nproc > 1 world (~2 host collectives per verb).
    # Now the engine exchanges a whole WINDOW of verbs in ONE allgather:
    # each rank packs its drained (kind, table, payload) prefix, the
    # ranks agree on the longest common verb prefix, and every rank then
    # holds EVERY rank's payloads for those verbs — so the merged
    # applies/gathers run from local data with no further host rounds,
    # and the single-process window optimizations return across ranks
    # (cross-rank add-coalescing via ProcessAddRunParts, union-gather
    # get-dedup via ProcessGetWindowParts). This restores the
    # reference's per-rank independence economics (worker.cpp:30-52,
    # server.cpp:23-58: requests fan out and apply as they arrive)
    # under the SPMD collective contract: every process still issues
    # the same verb sequence, but now pays ~2 host rounds per WINDOW
    # instead of ~2 per verb (multihost.STATS counts them;
    # tests/test_windowed_multihost.py holds the count).
    #
    # Ordering semantics match the single-process window: a table's
    # window Adds apply at its FIRST Add position (a Get queued after
    # that observes more progress — legal, every coalesced Add was
    # already enqueued when the Get was); Gets group per (table,
    # before/after-the-add-run segment) so no Get ever observes LESS
    # than strict order would show it. Non-verb messages (StoreLoad,
    # barriers, FinishTrain) split the window exactly as before and
    # dispatch in strict global order — their position in the verb
    # stream is lockstep because prefix processing is.
    #
    # Round 6 — adaptive transport: the window rides the FLAT BINARY
    # codec (parallel/wire.py) instead of pickle, and per Add verb the
    # engine picks the wire the reference's allreduce engine would
    # (size-adaptive, allreduce_engine.cpp:31-55): small payloads stay
    # on the host staging allgather; large eligible payloads ship only
    # their dtype/shape metadata and the VALUES ride the table's
    # device-parts collectives (-window_transport /
    # -window_device_min_bytes; the crossover behind the default is a
    # CPU-backend measurement, see the flags' comment above).

    def _mh_windows(self, batch) -> None:
        """Process drained messages through collective windows until
        nothing remains (blocking in the exchange while peers catch up
        is the protocol's flow control, exactly as the r4 per-verb
        collectives blocked). Verbs beyond an exchange's agreed prefix
        stay in the local deque and lead the NEXT exchange — the loop
        always drains fully before returning.

        Round 7: with ``-mv_pipeline`` (default) the exchange half runs
        on the dedicated stage thread and THIS thread becomes the apply
        stage — window N applies while window N+1 exchanges whenever
        the overlap gate allows (see _ExchangeStage). The serial path
        below is byte-identical to the round-5/6 engine.

        A DeadlineExceeded from the exchange (peer gone / diverged,
        -mv_deadline_s set) fails EVERY drained message — their waiters
        raise instead of hanging — and then propagates with its fatal
        mark so the actor poisons itself: after an abandoned collective
        this rank's collective stream is unsound.

        ELASTIC EXCEPTION (round 10): a MembershipChanged — a peer's
        heartbeat lease expired, confirmed by the coordinator when the
        exchange deadline consulted it — is NOT fatal when the elastic
        plane can transition: the engine rolls every table back to the
        retained snapshot cut on the shrunk world's mesh, re-bases the
        exchange stream (SEQ 0, caps dropped, stage retired) and stays
        healthy; the drained messages fail with the TYPED error (their
        effects were rolled back with everything after the cut) so the
        worker re-runs from its last elastic sync point — continuity,
        not a full-world restart."""
        pending: Deque[Message] = collections.deque(batch)
        try:
            try:
                if _pipeline_flag():
                    self._mh_pipelined(pending)
                else:
                    self._mh_windows_inner(pending)
            except MembershipChanged as exc:
                from multiverso_tpu import elastic
                if self._ex_stage is not None:
                    st = self._ex_stage
                    st.poison()
                    st.dead = st.dead or exc
                    self._ex_stage = None
                if not elastic.engine_transition(self, exc):
                    raise       # no plane / no cut: the fatal path below
                for m in pending:
                    m.reply(exc)
                return
        except Exception as exc:
            # ANY escape aborts the stream mid-window — an abandoned
            # exchange (DeadlineExceeded), an exhausted frame retry or
            # corrupted barrier marker (WireCorruption), a desync/
            # divergence CHECK (FatalError) — and all of them leave
            # this rank's collective position unsound: fail every
            # drained waiter (per-position errors never escape; they
            # reply locally), then poison the actor so no further
            # collectives are issued from a desynced stream. The
            # pipelined path keeps ``pending`` holding every message
            # currently owned by EITHER stage, so both drain here —
            # and the stage is killed so it issues no further
            # collectives from the desynced stream.
            if self._ex_stage is not None:
                self._ex_stage.poison()
            # forensics: the abort itself becomes a ring event, then
            # the whole ring hits disk (when -mv_diag_dir is set) so a
            # diverged 2-proc world leaves per-rank dumps that
            # telemetry/forensics.py can align — BEFORE waiters are
            # failed, so a fast-exiting worker can't beat the dump
            tflight.record("engine.fatal", seq=self._mh_seq,
                           epoch=self.window_epoch,
                           mepoch=multihost.membership_epoch(),
                           stream=self.mh_stream,
                           detail=f"{type(exc).__name__}: "
                                  f"{exc}"[:200])
            tflight.dump_failure(
                f"engine window stream abort ({type(exc).__name__})")
            for m in pending:
                m.reply(exc)
            exc.mv_fatal = True
            raise

    # -- round 7: PIPELINED window engine (apply stage) ---------------------

    def _mh_pipelined(self, fed: "Deque[Message]") -> None:
        """Apply stage + scheduler: feed admitted messages to the
        exchange stage in admission order, keep draining the mailbox
        while exchanges are in flight (the NEXT window forms while the
        current one is still on the wire — this is where the overlap
        comes from), and apply completed windows strictly in emission
        (= SEQ) order. ``fed`` always holds every message owned by the
        pipeline, oldest first — the caller's error path fails exactly
        those."""
        stage = self._ex_stage
        if stage is None or stage.dead is not None:
            stage = self._ex_stage = _ExchangeStage(self)
        for m in fed:
            self._pl_feed(stage, m)
        deadline = fdeadline.timeout_or_none()
        stall_s = 0.0
        while fed:
            # opportunistic drain: verbs arriving during an exchange
            # join the stage's pending deque and form the next window
            # (bounded per spin so applies are never starved). Batched
            # envelopes flatten HERE too — without the expansion an
            # envelope would feed the stage as a barrier, a per-rank
            # timing artifact that diverges the SPMD streams (review
            # catch, round 19)
            for _ in range(64):
                ok, m = self.mailbox.TryPop()
                if not ok:
                    break
                if m.msg_type is MsgType.Request_MultiVerb:
                    for mm in self._expand_multi([m]):
                        if self._admit(mm):
                            fed.append(mm)
                            self._pl_feed(stage, mm)
                    continue
                self.note_dequeue(m)
                if self._admit(m):
                    fed.append(m)
                    self._pl_feed(stage, m)
            ok, item = stage.out.TryPop()
            if not ok:
                ok, item = stage.out.Pop(timeout=_PL_POLL_S)
            if not ok:
                # exchange still in flight (or waiting for peers). The
                # stage bounds its own collective; this guard catches a
                # stage that died without emitting (interpreter
                # teardown) — grace past the stage's own deadline so
                # its richer error wins the race when both fire.
                stall_s += _PL_POLL_S
                if deadline is not None and stall_s > deadline + 1.0:
                    fdeadline.raise_deadline(
                        "pipelined window flush (exchange stage stalled)",
                        fatal=True)
                continue
            stall_s = 0.0
            kind = item[0]
            if kind == "error":
                raise item[1]
            try:
                if kind == "barrier":
                    head = item[1]
                    CHECK(fed.popleft() is head,
                          "pipeline completion order desync (engine bug)")
                    self.window_barrier_splits += 1
                    self._t_splits.inc()
                    self._dispatch(head)
                else:
                    (_, mine, windows, prefix, descs0, t0, win_ctx,
                     ph, fcause) = item
                    # a fence-free window is host-local on EVERY rank
                    # (the same rank-agreed decision that allowed the
                    # overlap) — exactly the windows whose tables may
                    # apply concurrently without reordering collectives
                    self._pl_apply(mine, windows, prefix, descs0,
                                   win_ctx, ph,
                                   parallel_ok=fcause is None)
                    for m in mine:
                        CHECK(fed.popleft() is m,
                              "pipeline completion order desync "
                              "(engine bug)")
                    self._t_window_s.observe(_time.perf_counter() - t0)
            finally:
                # ALWAYS lift the stage's fence/depth gate — even when a
                # fatal apply error is about to poison the actor, the
                # stage must not hang inside _wait_applied
                stage.note_applied()
            if self._ex_stage is not stage:
                # an elastic rebase retired the stage inside that
                # barrier dispatch (epoch transition): the pipeline's
                # remaining contents re-anchor to the NEW world —
                # single-member worlds drain through the local window
                # path, otherwise a fresh stage (bound to the new
                # world rank, SEQ 0) takes over and the verbs re-lead
                # the new epoch's stream
                if self._elastic_post_transition(fed):
                    return
                stage = self._ex_stage = _ExchangeStage(self)
                for m in fed:
                    self._pl_feed(stage, m)

    def _pl_feed(self, stage: _ExchangeStage, m: Message) -> None:
        # envelopes must have been flattened by every feeding path —
        # one reaching the stage would become a bogus cross-rank
        # barrier position
        CHECK(m.msg_type is not MsgType.Request_MultiVerb,
              "unexpanded multi-verb envelope fed to the exchange "
              "stage (engine bug)")
        if m.msg_type in (MsgType.Request_Add, MsgType.Request_Get):
            stage.feed_verbs([m])
        else:
            stage.feed_barrier(m)

    def _pl_apply(self, verbs, windows, prefix, descs0, win_ctx,
                  ph=None, parallel_ok: bool = False) -> None:
        """Apply one exchanged window on the actor thread, recording
        the apply interval for the overlap telemetry (and closing the
        window's phase record — ``ph`` rode the stage's out queue from
        the exchange thread)."""
        t0 = _time.perf_counter()
        self._apply_since = t0
        if ph is not None:
            ph["a_start_m"] = t0
        try:
            with ttrace.span("server.window.apply", cat="server",
                             parent=win_ctx, args={"verbs": prefix}):
                self._mh_apply_window(verbs, windows, prefix, descs0,
                                      seq=(ph or {}).get("seq", -1),
                                      parallel_ok=parallel_ok)
        finally:
            now = _time.perf_counter()
            self._apply_since = 0.0
            self.apply_busy_s += now - t0
            st = self._ex_stage
            b0 = st.busy_since if st is not None else 0.0
            if b0:
                # an exchange is STILL in flight as this apply ends:
                # record the stretch both were busy (the stage records
                # the symmetric case when its exchange ends first)
                self._note_overlap(max(0.0, now - max(b0, t0)))
            self.window_epoch += 1
            if ph is not None:
                ph["apply"] = now - t0
                self._ph_emit(ph, prefix)
            tflight.record("window.applied", seq=self._mh_seq,
                           epoch=self.window_epoch,
                           mepoch=multihost.membership_epoch(),
                           stream=self.mh_stream,
                           detail=f"{prefix}v")

    def _mh_windows_inner(self, pending: "Deque[Message]") -> None:
        while pending:
            head = pending[0]
            if head.msg_type not in (MsgType.Request_Add,
                                     MsgType.Request_Get):
                # window barrier: strict-order dispatch (may itself run
                # collectives — matched, every rank hits it at the same
                # global verb position). The marker exchange makes a
                # cross-rank head MISMATCH (this rank at a barrier, a
                # peer exchanging verbs) fail the loud SPMD CHECK
                # instead of deadlocking in mismatched collectives.
                self._mh_check_barrier_head(head)
                pending.popleft()
                self.window_barrier_splits += 1
                self._t_splits.inc()
                self._dispatch(head)
                if self._elastic_post_transition(pending):
                    return
                continue
            verbs = []
            for m in pending:
                if m.msg_type in (MsgType.Request_Add, MsgType.Request_Get):
                    verbs.append(m)
                else:
                    break
            done = self._mh_collective_window(verbs)
            for _ in range(done):
                pending.popleft()

    #: byte budget for one exchange's packed payloads: verbs beyond it
    #: wait for the next exchange. Bounds the re-ship cost when ranks
    #: drain raggedly (a short peer prefix would otherwise make every
    #: retry re-pickle + re-transmit the whole pending run — O(W^2)
    #: bytes for a W-verb burst of large payloads).
    MH_WINDOW_BYTES = 4 << 20

    #: one shared byte-accounting rule with the worker-side telemetry
    #: counters (wire.payload_nbytes) — the budget and the counters
    #: must never drift
    _payload_bytes = staticmethod(wire.payload_nbytes)

    def _mh_check_barrier_head(self, head: Message) -> None:
        """Exchange a head-kind marker for a non-verb window head. Every
        rank reaches the same barrier at the same stream position in a
        legal SPMD program, so the markers agree; a divergent program
        (one rank at a StoreLoad while a peer exchanges verbs) trips the
        loud CHECK on every rank instead of stranding the verb rank in
        an unmatched collective. Best-effort when standing caps have
        already diverged across mismatched keys: the exchange itself
        then fails at the runtime layer (mismatched buffer shapes) —
        still an error, not a silent hang."""
        marker = wire.encode_head_barrier(int(head.msg_type))
        blobs = self._bounded_collective(
            lambda: multihost.capped_exchange(marker, self._mh_caps,
                                              "HEAD_B",
                                              channel=self.mh_channel),
            "window head-marker exchange")
        # seq of the NEXT exchange: barriers do not advance the SEQ
        # counter, so forensics aligns a barrier against the verbs a
        # diverged peer exchanged at that same seq
        tflight.record("barrier", seq=self._mh_seq,
                       epoch=self.window_epoch,
                       mepoch=multihost.membership_epoch(),
                       stream=self.mh_stream,
                       detail=MsgType(head.msg_type).name)
        kinds = [wire.decode_head_kind(b) for b in blobs]
        CHECK(all(k == kinds[0] for k in kinds),
              f"multi-process window heads diverge: {kinds} — every "
              f"process must reach the same barrier/verb at the same "
              f"stream position (the SPMD collective contract)")

    def _mh_transport(self) -> str:
        mode = _window_transport_flag()
        CHECK(mode in ("auto", "host", "device"),
              f"-window_transport must be auto/host/device, got {mode!r}")
        return mode

    def _mh_maybe_defer(self, tid: int, payload: dict, mode: str,
                        min_bytes: int) -> dict:
        """Transport selection, per Add verb at pack time (the
        reference's payload-size-adaptive wire pick): when the device
        wire is selected and the table can apply this payload through
        its device-parts collectives, replace the ``values`` array with
        a wire.DeferredArray — the exchange then ships only dtype/shape
        metadata and the bytes ride the device. The decision is
        rank-local (peers may differ); the APPLY decision is taken from
        the exchanged metadata (any rank deferred -> device path), so
        every rank still runs the identical program. ``mode`` and
        ``min_bytes`` are parsed ONCE per window by the caller (flags
        cannot change mid-window)."""
        if mode == "host":
            return payload
        v = payload.get("values")
        if isinstance(v, wire.DeferredArray):   # re-led window leftover
            return payload
        if not isinstance(v, np.ndarray):
            return payload
        if not wire.dtype_wire_safe(v.dtype):
            # extension dtypes (bfloat16 &c) have no flat wire header;
            # their payloads stay whole on the host pickle fallback
            return payload
        if mode == "auto" and v.nbytes < min_bytes:
            return payload
        try:
            table = self.store_[tid]
        except Exception:
            return payload      # bad table id: the apply path reports it
        if not table.device_wire_add_ok(payload):
            return payload
        out = dict(payload)
        out["values"] = wire.DeferredArray.of(v)
        self._t_defer.inc()
        self._t_dev_bytes.inc(v.nbytes)
        return out

    def _mh_collective_window(self, verbs) -> int:
        """One collective window: exchange, agree on the common prefix,
        execute it from the exchanged parts. Returns how many of this
        rank's ``verbs`` were processed (>= 1)."""
        _t_start = _time.perf_counter()
        with ttrace.span("server.window", cat="server",
                         parent=verbs[0].trace_ctx,
                         args={"verbs": len(verbs)}):
            done = self._mh_collective_window_inner(verbs)
        self._t_window_s.observe(_time.perf_counter() - _t_start)
        return done

    #: collective re-exchange attempts after a CRC-detected corrupt
    #: frame. Recovery relies on SYMMETRIC detection — every rank sees
    #: the same round corrupted, which holds for fabric-level faults of
    #: the shared round and (by construction) for the seeded chaos
    #: schedule — so each rank re-enters the exchange in lockstep. An
    #: ASYMMETRIC corruption leaves the detecting rank raising
    #: WireCorruption after its retries while peers move on: a loud
    #: error, bounded on the peers by -mv_deadline_s — never silently
    #: decoded garbage.
    MH_WIRE_RETRIES = 2

    def _mh_exchange_decode(self, local, my_rank: int,
                            ph: Optional[dict] = None) -> list:
        """Encode + exchange + decode one window, deadline-bounded,
        retrying the full (collective) exchange when a received frame
        fails its CRC32 trailer. Returns every rank's verb list.

        ``ph`` (perf forensics, round 11): accumulates this window's
        encode/exchange/decode phase seconds — exchange split into
        total wall vs time BLOCKED IN THE COLLECTIVE
        (multihost.last_exchange_stats), whose done-stamps anchor the
        cross-rank clock alignment. CRC retries accumulate into the
        same phases (the retry cost is real window cost); the stamps
        kept are the SUCCESSFUL exchange's."""
        last_exc = None
        for attempt in range(1 + self.MH_WIRE_RETRIES):
            # flat binary codec (parallel/wire.py): pickle's object-
            # graph walk + buffer copies were pure overhead for payloads
            # that are already contiguous arrays; decode below is
            # zero-copy. server.wire.encode_s times the CODEC only
            _t0 = _time.perf_counter()
            blob = wire.encode_window(local, seq=self._mh_seq)
            _enc_s = _time.perf_counter() - _t0
            self._t_encode_s.observe(_enc_s)
            if ph is not None:
                ph["encode"] = ph.get("encode", 0.0) + _enc_s
            cz = chaos.get()
            if cz is not None:
                bad = cz.corrupt_blob(blob)
                if bad is not None:
                    blob = bad
            self._t_host_bytes.inc(len(blob))
            # standing-cap exchange keyed by the window HEAD verb: the
            # head is the same global verb on every rank (FIFO + common-
            # prefix processing), and per-head payload sizes are stable
            # in steady loops — so the exchange stays on the 1-round path
            _tx = _time.perf_counter()
            with ttrace.span("server.window.exchange", cat="server",
                             args={"bytes": len(blob)}):
                blobs = self._bounded_collective(
                    lambda: multihost.capped_exchange(
                        blob, self._mh_caps, (local[0][0], local[0][1]),
                        channel=self.mh_channel),
                    "window exchange")
            xs = multihost.last_exchange_stats()
            # plain-attr accumulation (one float add, no stamps needed):
            # the watchdog straggler rule's collective-wait input
            self.xw_busy_s += xs.get("coll_s", 0.0)
            if ph is not None:
                ph["x"] = ph.get("x", 0.0) + _time.perf_counter() - _tx
                ph["xw"] = ph.get("xw", 0.0) + xs["coll_s"]
                # rendezvous anchor: every rank leaves this allgather
                # at ~the same instant (critpath's clock-offset source)
                ph["x_done_m"] = xs["done_m"]
                ph["x_done_w"] = xs["done_w"]
            _t0 = _time.perf_counter()
            try:
                windows: list = []
                for i, b in enumerate(blobs):
                    if i == my_rank:
                        # our own verbs verbatim — no decode round-trip,
                        # and deferred values keep their .local arrays.
                        # COMPRESSED values are the one exception: every
                        # rank must apply the identical dequantized
                        # reconstruction (the peers decode eagerly in
                        # the flat codec; we run the same envelope
                        # decode here), else lossy codecs would diverge
                        # the SPMD replicas
                        windows.append(compress.materialize_window(local))
                        continue
                    head_kind, head_mt = wire.decode_head_kind(b)
                    CHECK(head_kind == "window",
                          f"multi-process window heads diverge: rank {i} "
                          f"is at a non-verb barrier (msg_type {head_mt}) "
                          f"while rank {my_rank} exchanges verbs — every "
                          f"process must reach the same stream position "
                          f"(the SPMD collective contract)")
                    peer_seq, decoded = wire.decode_window_seq(b)
                    CHECK(peer_seq == (self._mh_seq & 0xFFFFFFFF),
                          f"window exchange desynchronized: rank {i} is "
                          f"at exchange {peer_seq}, rank {my_rank} at "
                          f"{self._mh_seq} — a rank re-entered the "
                          f"exchange alone (asymmetric frame corruption "
                          f"retry?); the stream cannot be trusted")
                    windows.append(decoded)
            except WireCorruption as exc:
                last_exc = exc
                tflight.record("wire.crc_retry", seq=self._mh_seq,
                               epoch=self.window_epoch,
                               mepoch=multihost.membership_epoch(),
                               stream=self.mh_stream,
                               detail=f"attempt{attempt + 1}")
                Log.Error("window exchange frame corrupt (attempt "
                          "%d/%d): %r — re-exchanging", attempt + 1,
                          1 + self.MH_WIRE_RETRIES, exc)
                continue
            _dec_s = _time.perf_counter() - _t0
            self._t_decode_s.observe(_dec_s)
            if ph is not None:
                ph["dec"] = ph.get("dec", 0.0) + _dec_s
            self._mh_seq += 1
            self.mh_window_exchanges += 1
            self._t_exchanges.inc()
            return windows
        # retries exhausted: this rank cannot re-enter the exchange
        # again without desyncing from peers — fatal for the actor
        last_exc.mv_fatal = True
        raise last_exc

    def _mh_pack_window(self, verbs):
        """Pack a window from ``verbs`` under the byte budget; returns
        ``(local, used)`` — the packed (kind, table, payload) records
        and the messages they came from (always >= 1). The budget
        counts what rides the HOST wire, so values deferred to the
        device wire (DeferredArray — dtype/shape header only) cost
        ~nothing here and a device-transport burst of large Adds still
        coalesces into one exchange."""
        mode = self._mh_transport()
        min_bytes = _window_device_min_bytes_flag()
        local = []
        used = []
        packed = 0
        for i, m in enumerate(verbs):
            kind = "A" if m.msg_type is MsgType.Request_Add else "G"
            payload = m.payload
            if kind == "A":
                payload = self._mh_maybe_defer(m.table_id, payload,
                                               mode, min_bytes)
                # -mv_compress: int8-quantize a lossy-opted table's Add
                # values for the host wire (parallel/compress.py tagged
                # envelope; a no-op for deferred/already-compressed
                # values). The apply side reconstructs through ONE
                # decode on every rank, our own included — see the
                # materialize step in _mh_exchange_decode
                payload = compress.pack_window_values(m.table_id,
                                                      payload)
                if payload is not m.payload:
                    # keep the deferred/compressed form on the message:
                    # a verb re-led after a short peer prefix / budget
                    # cut must not re-defer, re-compress (or re-count)
                    # on the next pack pass
                    m.payload = payload
            nbytes = self._payload_bytes(payload)
            if packed + nbytes > self.MH_WINDOW_BYTES and i > 0:
                # over-budget verb waits for the next exchange — its
                # bytes stay OUT of this window's budget accounting
                break
            packed += nbytes
            local.append((kind, m.table_id, payload))
            used.append(m)
        self._t_budget.set(packed)
        tflight.record("window.admitted", seq=self._mh_seq,
                       epoch=self.window_epoch,
                       mepoch=multihost.membership_epoch(),
                       stream=self.mh_stream,
                       detail=f"{len(used)}v/{packed}B")
        return local, used

    def _mh_fence_cause(self, descs0, windows, prefix) -> Optional[str]:
        """None when THIS window's apply runs entirely on the host —
        the pipelined engine's overlap gate — else the FENCE_CAUSES
        entry naming why it must fence (fence-cause profiling). Decided
        from EXCHANGED data (every rank holds identical windows) plus
        table state that evolves at lockstep verb positions
        (tables/base.py mh_apply_is_local contract), so every rank
        gates identically: overlap never pairs an apply-side device
        collective on one rank with an exchange-thread allgather on
        another."""
        tables_ok: Dict[int, bool] = {}
        cause = None
        for kind, tid in descs0:
            ok = tables_ok.get(tid)
            if ok is None:
                try:
                    ok = bool(self.store_[tid].mh_apply_is_local())
                except Exception:
                    ok = False   # bad table id: per-position error path
                tables_ok[tid] = ok
            if not ok:
                cause = "nonlocal_table"
                break
        if cause is None:
            for w in windows:
                for _, _, payload in w[:prefix]:
                    if wire.payload_has_deferred(payload):
                        cause = "device_wire"  # device values: collective
                        break
                if cause is not None:
                    break
        # round 12 — sharded multi-process worlds: a COLLECTIVE apply
        # (device program / gloo round inside the apply) is only sound
        # when ONE stream exists to order it. With N shard streams
        # live, shard A's collective apply could interleave with shard
        # B's in a different order on different ranks — loud CHECK
        # (with advice) instead of a silent rank-divergent deadlock.
        # (Cross-stream CUT payloads are exempt by construction: every
        # stream is fenced while they run.)
        if cause is not None:
            CHECK(self.mh_single_collective_stream,
                  f"window requires a collective apply ({cause}) but "
                  f"the engine runs {getattr(self, '_shard_cap', '>1')}"
                  f" shard streams in a multi-process world — "
                  f"collective applies need ONE ordered stream: run "
                  f"-mv_engine_shards=1, or keep every table's apply "
                  f"host-local (-window_transport=host + host-backed "
                  f"tables)")
        return cause

    def _mh_collective_window_inner(self, verbs) -> int:
        my_rank = multihost.world_rank()
        ph = {} if self._phases_on() else None
        _tp = _time.perf_counter()
        local, used = self._mh_pack_window(verbs)
        if ph is not None:
            ph["pack"] = _time.perf_counter() - _tp
        windows = self._mh_exchange_decode(local, my_rank, ph)
        prefix = min(len(w) for w in windows)
        descs = [[(k, t) for k, t, _ in w[:prefix]] for w in windows]
        self._flight_exchanged(descs, my_rank)
        CHECK(all(d == descs[0] for d in descs),
              f"multi-process verb streams diverge inside a window: "
              f"{descs} — every process must issue the same table-verb "
              f"sequence (the SPMD collective contract)")
        seq = self._mh_seq - 1
        if ph is not None:
            ph["seq"] = seq
            ph["mepoch"] = multihost.membership_epoch()
            ph["a_start_m"] = _time.perf_counter()
        _ta = _time.perf_counter()
        self._mh_apply_window(used[:prefix], windows, prefix, descs[0],
                              seq=seq)
        self.apply_busy_s += _time.perf_counter() - _ta
        self.window_epoch += 1
        if ph is not None:
            ph["apply"] = _time.perf_counter() - ph["a_start_m"]
            self._ph_emit(ph, prefix)
        tflight.record("window.applied", seq=self._mh_seq,
                       epoch=self.window_epoch,
                       mepoch=multihost.membership_epoch(),
                       stream=self.mh_stream,
                       detail=f"{prefix}v")
        return prefix

    def _mh_apply_window(self, verbs, windows, prefix, descs0,
                         seq: int = -1,
                         parallel_ok: bool = False) -> None:
        """Apply one exchanged window's agreed prefix: cross-rank
        coalesced add runs + deduped get groups, replies to this rank's
        own messages. Shared by the serial engine and the pipelined
        apply stage — the semantics (ordering, grouping, error routing)
        are identical in both. ``seq`` is this window's exchange SEQ
        (perf forensics: keys the per-table apply attribution; -1 when
        phases are off).

        ``parallel_ok`` (round 12): DIFFERENT tables' segments of this
        window apply concurrently on the -mv_apply_workers pool. Only
        set for windows whose apply is host-local on every rank (the
        pipelined overlap gate's rank-agreed decision): per-table op
        order stays serial — determinism untouched — while a window
        that fenced (collective applies) keeps the strict interleaved
        position order below, because collective device/host programs
        must issue in one agreed order."""
        my_rank = multihost.world_rank()
        self.mh_window_verbs += prefix
        self._t_verbs.inc(prefix)
        # chaos rehearsal: a per-site APPLY delay on this rank only — a
        # perf fault, not a correctness one (the stream stays lockstep;
        # the delay models a slow apply stage, the straggler the
        # critpath drill must attribute). Consulted once per window.
        cz = chaos.get()
        if cz is not None:
            _delay = cz.apply_delay()
            if _delay > 0.0:
                _time.sleep(_delay)
        # round 20 — policy routing inputs: always-on per-table tallies
        # (one dict add per agreed position + two perf_counter calls
        # per window op — inside the 2% blocking-round budget)
        for _k, _tid in descs0:
            self.table_verbs[_tid] = self.table_verbs.get(_tid, 0) + 1
        tbl = {}
        # group per table: Add positions, and Get positions split into
        # the before/after segment around the table's one add-run
        add_pos: Dict[int, list] = {}
        for i, (kind, tid) in enumerate(descs0):
            if kind == "A":
                add_pos.setdefault(tid, []).append(i)
        get_groups: Dict[tuple, list] = {}   # (tid, segment) -> positions
        for i, (kind, tid) in enumerate(descs0):
            if kind == "G":
                seg = 0 if (tid not in add_pos or i < add_pos[tid][0]) else 1
                get_groups.setdefault((tid, seg), []).append(i)
        parts_at = [[w[i][2] for w in windows] for i in range(prefix)]
        # ONE ordered op list (first-position order, per-table dedup +
        # before/after-add get segmentation) feeds BOTH branches, so
        # the serial and parallel engines cannot drift on the window
        # grammar. The serial branch executes it in strict position
        # order — collective applies (fenced windows) must issue in
        # one agreed order; the parallel branch regroups per table.
        ops = self._mh_window_ops(descs0, add_pos, get_groups)
        n_tables = len({tid for _, tid, _ in ops})
        if (parallel_ok and n_tables > 1 and _apply_workers_flag() > 1):
            self._mh_apply_parallel(ops, parts_at, verbs, my_rank, tbl)
        else:
            self._mh_run_ops(ops, parts_at, verbs, my_rank, tbl)
        for (_tid, _k), _v in tbl.items():
            self.table_apply_s[_tid] = (self.table_apply_s.get(_tid, 0.0)
                                        + _v)
        if tbl and self._phases_on():
            self._ph_tables(tbl, seq, multihost.membership_epoch())

    @staticmethod
    def _mh_window_ops(descs0, add_pos, get_groups) -> list:
        """The window's op list in first-position order:
        ``("A", tid, positions)`` once per table's merged add run,
        ``("G", tid, positions)`` once per (table, before/after-add
        segment) get group. Within a table the order is its serial
        apply order (seg-0 gets precede the add run precede seg-1
        gets, because their first positions do)."""
        ops = []
        applied: set = set()
        served: set = set()
        for i, (kind, tid) in enumerate(descs0):
            if kind == "A":
                if tid in applied:
                    continue
                applied.add(tid)
                ops.append(("A", tid, add_pos[tid]))
            else:
                seg = (0 if (tid not in add_pos
                             or i < add_pos[tid][0]) else 1)
                if (tid, seg) in served:
                    continue
                served.add((tid, seg))
                ops.append(("G", tid, get_groups[(tid, seg)]))
        return ops

    def _mh_run_ops(self, ops, parts_at, verbs, my_rank: int,
                    tbl) -> dict:
        """Execute window ops in the given order (the shared worker
        body of the serial branch and each parallel job); accumulates
        per-(table, verb) apply seconds into ``tbl`` when given and
        also returns them (parallel jobs pass a private dict)."""
        for kind, tid, positions in ops:
            _tt = _time.perf_counter() if tbl is not None else 0.0
            if kind == "A":
                with ttrace.span("server.window.add_run", cat="server",
                                 args={"table_id": tid,
                                       "positions": len(positions)}):
                    self._mh_add_run(tid, positions, parts_at, verbs,
                                     my_rank)
            else:
                with ttrace.span("server.window.get_group",
                                 cat="server",
                                 args={"table_id": tid}):
                    self._mh_get_group(tid, positions, parts_at,
                                       verbs, my_rank)
            if tbl is not None:
                k = (tid, kind)
                tbl[k] = tbl.get(k, 0.0) + _time.perf_counter() - _tt
        return tbl

    def _ensure_apply_pool(self) -> "_ApplyPool":
        """The apply-stage worker pool at the LIVE ``-mv_apply_workers``
        size (round 20): the policy plane tunes the flag at a fenced
        cut, and the next parallel window rebuilds the pool when the
        size changed. Safe between windows on the actor thread — every
        prior window's jobs were waited for, so the retired pool's
        queue is empty when it closes; its daemon workers just exit."""
        want = max(2, min(_apply_workers_flag(), 16))
        pool = self._apply_pool
        if pool is None or pool.workers != want:
            if pool is not None:
                pool.shutdown()
            pool = self._apply_pool = _ApplyPool(want, self.name)
        return pool

    def _mh_apply_parallel(self, ops, parts_at, verbs, my_rank: int,
                           tbl) -> None:
        """Round 12 — the parallel apply: the shared op list regrouped
        into per-table ordered jobs (a table's serial order is kept)
        run concurrently across tables on the worker pool. Only
        reached for host-local windows (see _mh_apply_window), where
        different tables share no state and issue no collectives, so
        the cross-table interleaving the serial branch produces was
        never observable."""
        jobs: Dict[int, list] = {}
        for op in ops:
            jobs.setdefault(op[1], []).append(op)
        pool = self._ensure_apply_pool()
        job_lists = list(jobs.values())
        # the LAST job runs inline on the actor thread: one fewer
        # handoff, and the pool only ever carries n_tables - 1 jobs
        boxes = [pool.submit(lambda j=j: self._mh_run_ops(
            j, parts_at, verbs, my_rank,
            {} if tbl is not None else None))
            for j in job_lists[:-1]]
        # pool-utilization accounting (watchdog plane): jobs handed to
        # the worker pool vs the one job that always runs inline here
        self._t_pool_jobs.inc(len(boxes))
        self._t_pool_inline.inc()
        results = [self._mh_run_ops(job_lists[-1], parts_at, verbs,
                                    my_rank,
                                    {} if tbl is not None else None)]
        deadline = fdeadline.timeout_or_none()
        t0 = _time.perf_counter()
        for box in boxes:
            left = (None if deadline is None
                    else max(0.0, deadline - (_time.perf_counter() - t0)))
            if not box["done"].wait(left):
                fdeadline.raise_deadline(
                    "parallel window apply (a table's apply job never "
                    "finished)", fatal=True)
            if "error" in box:
                raise box["error"]
            results.append(box.get("result"))
        if tbl is not None:
            for local in results:
                for k, v in (local or {}).items():
                    tbl[k] = tbl.get(k, 0.0) + v

    def _mh_add_run(self, tid: int, positions, parts_at, verbs,
                    my_rank: int) -> None:
        """A table's window-worth of collective Adds: merged across
        positions AND ranks when the table accepts, per-position
        otherwise. Positions whose values rode the DEVICE wire (any
        rank's part holds a DeferredArray — visible identically on
        every rank from the exchanged metadata) apply through the
        table's device-parts collectives and never join a host merge —
        as ONE merged device round when the table offers
        ProcessAddRunPartsDevice, per position otherwise.
        Failures reply to this rank's own messages only — every rank
        reaches identical decisions from identical parts."""
        try:
            table = self.store_[tid]
        except Exception as exc:
            for p in positions:
                verbs[p].reply(exc)
            return
        deferred = {p for p in positions
                    if any(isinstance(q.get("values"), wire.DeferredArray)
                           for q in parts_at[p])}
        # the HOST-wire subset still merges when device-wire positions
        # share the run — one large deferred Add must not demote the
        # small-burst positions back to per-position dispatches
        host_pos = [p for p in positions if p not in deferred]
        pending = list(positions)
        if len(host_pos) > 1:
            try:
                merged = bool(table.ProcessAddRunParts(
                    [parts_at[p] for p in host_pos], my_rank))
            except Exception as exc:
                Log.Error("table %d merged parts Add failed: %r", tid, exc)
                for p in pending:
                    verbs[p].reply(exc)
                return
            if merged:
                self.mh_add_dispatches += 1
                self.mh_add_run_merged += 1
                self._t_dispatch.inc()
                self._t_merged.inc()
                for p in host_pos:
                    verbs[p].reply(None)
                pending = [p for p in pending if p in deferred]
        # ...and the DEVICE-wire subset merges too: one collective parts
        # round for the run's deferred positions when the table offers
        # ProcessAddRunPartsDevice (decisions from exchanged metadata,
        # so every rank merges or declines identically)
        dev_pos = [p for p in pending if p in deferred]
        if len(dev_pos) > 1:
            try:
                dev_merged = bool(table.ProcessAddRunPartsDevice(
                    [parts_at[p] for p in dev_pos], my_rank))
            except Exception as exc:
                Log.Error("table %d merged device Add failed: %r", tid, exc)
                for p in pending:
                    verbs[p].reply(exc)
                return
            if dev_merged:
                self.mh_add_dispatches += 1
                self.mh_add_run_merged += 1
                self.mh_device_wire_adds += len(dev_pos)
                self._t_dispatch.inc()
                self._t_merged.inc()
                for p in dev_pos:
                    verbs[p].reply(None)
                pending = [p for p in pending if p not in deferred]
        for p in pending:
            with monitor_region("SERVER_PROCESS_ADD"):
                try:
                    if p in deferred:
                        table.ProcessAddPartsDevice(parts_at[p], my_rank)
                        self.mh_device_wire_adds += 1
                    else:
                        table.ProcessAddParts(parts_at[p], my_rank)
                    self.mh_add_dispatches += 1
                    self._t_dispatch.inc()
                except Exception as exc:
                    Log.Error("table %d parts Add failed: %r", tid, exc)
                    verbs[p].reply(exc)
                    continue
            verbs[p].reply(None)

    def _mh_get_group(self, tid: int, positions, parts_at, verbs,
                      my_rank: int) -> None:
        """A (table, segment)'s collective Gets: one shared union gather
        when the table offers it, per-position otherwise."""
        try:
            table = self.store_[tid]
        except Exception as exc:
            for p in positions:
                verbs[p].reply(exc)
            return
        results = None
        if len(positions) > 1:
            try:
                results = table.ProcessGetWindowParts(
                    [parts_at[p] for p in positions], my_rank)
            except Exception as exc:
                Log.Error("table %d window parts Get failed: %r", tid, exc)
                for p in positions:
                    verbs[p].reply(exc)
                return
        if results is not None:
            CHECK(len(results) == len(positions),
                  "ProcessGetWindowParts result count mismatch")
            for p, res in zip(positions, results):
                verbs[p].reply(res)
            return
        for p in positions:
            with monitor_region("SERVER_PROCESS_GET"):
                try:
                    result = table.ProcessGetParts(parts_at[p], my_rank)
                except Exception as exc:
                    Log.Error("table %d parts Get failed: %r", tid, exc)
                    verbs[p].reply(exc)
                    continue
            verbs[p].reply(result)

    def _offer_add_run(self, table, payloads) -> bool:
        """What a run of two or more Adds is offered to: the table's
        merged run (a same-rows run summed, any other stacked)."""
        return table.ProcessAddRun(payloads)

    def _process_add_run(self, msgs) -> None:
        """Apply a table's window-worth of Adds: as ONE dispatch when the
        table accepts the run (``_offer_add_run``; the table validates
        BEFORE mutating and returns False to decline), per-message
        otherwise. An accepted run acknowledges every message after the
        apply; an exception replies to every message of the run."""
        if len(msgs) > 1:
            try:
                table = self.store_[msgs[0].table_id]
                merged = self._offer_add_run(table,
                                             [m.payload for m in msgs])
            except Exception as exc:
                # the run contract: state mutates only after validation,
                # so a raise here means the whole merged Add failed
                Log.Error("table %d merged Add failed: %r",
                          msgs[0].table_id, exc)
                for m in msgs:
                    m.reply(exc)
                return
            if merged:
                self._t_dispatch.inc()
                self._t_merged.inc()
                for m in msgs:
                    m.reply(None)
                return
        for m in msgs:
            self.ProcessAdd(m)

    @staticmethod
    def _get_dedup_key(m: Message, skip=()):
        """Hashable identity of a Get's request, or None when any payload
        part can't be keyed (those never dedup). Payload parts of a type
        in ``skip`` are no part of it."""
        parts = [m.table_id]
        for k in sorted(m.payload):
            v = m.payload[k]
            if isinstance(v, skip):
                continue
            if isinstance(v, np.ndarray):
                parts.append((k, v.dtype.str, v.shape, v.tobytes()))
            elif v is None or isinstance(v, (bool, int, float, str, bytes)):
                parts.append((k, v))
            elif isinstance(v, (GetOption, AddOption)):
                parts.append((k, repr(v)))
            else:
                return None
        return tuple(parts)

    def ProcessGet(self, msg: Message) -> None:
        with monitor_region("SERVER_PROCESS_GET"):
            try:
                # store_ lookup inside the try: a bad table id must reply
                # to THIS message, not escape and abandon the window
                result = self.store_[msg.table_id].ProcessGet(**msg.payload)
            except Exception as exc:
                # Deliver the failure to THIS request — critical when this
                # message is a drained cached message processed inside
                # another worker's request (SyncServer drain loops): the
                # actor-level fallback would mis-attribute the error to the
                # outer message and leave this one's waiter hung.
                Log.Error("table %d ProcessGet failed: %r", msg.table_id, exc)
                msg.reply(exc)
                return
            msg.reply(result)

    def _add_entry(self, msg: Message) -> None:
        """Request_Add enters the same window as Gets (coalescing — see
        _get_entry), in both engines."""
        self._get_entry(msg)

    def ProcessAdd(self, msg: Message) -> None:
        with monitor_region("SERVER_PROCESS_ADD"):
            try:
                # store_ lookup inside the try (see ProcessGet)
                self.store_[msg.table_id].ProcessAdd(**msg.payload)
            except Exception as exc:
                Log.Error("table %d ProcessAdd failed: %r", msg.table_id, exc)
                msg.reply(exc)
                return
            self._t_dispatch.inc()
            msg.reply(None)

    def ProcessFinishTrain(self, msg: Message) -> None:
        msg.reply(None)

    def _store_load_entry(self, msg: Message) -> None:
        """Engine-cut payload runner (StoreLoad AND Publish): run the
        message's fn at this stream position, reply its result."""
        try:
            msg.reply(msg.payload["fn"]())
        except Exception as exc:
            Log.Error("engine-cut payload fn (%s) failed: %r",
                      msg.msg_type.name, exc)
            msg.reply(exc)

    @staticmethod
    def GetServer(num_workers: int) -> "Server":
        """Factory mirroring reference server.cpp:224-232 — extended
        (round 12) with the sharded engine: ``-mv_engine_shards``
        resolves through :func:`engine_shard_cap`, and a cap > 1
        builds the router-fronted ShardedServer (1 = today's single
        engine byte-for-byte)."""
        if GetFlag("sync"):
            Log.Debug("Create a sync server")
            return SyncServer(num_workers)
        cap = engine_shard_cap()
        if cap > 1:
            Log.Debug("Create a sharded async server (%d shard slots)",
                      cap)
            return ShardedServer(cap)
        Log.Debug("Create an async server")
        return Server()


def requested_engine_channels() -> int:
    """How many independent wire channels the engine WANTS for this
    world — consulted by Zoo.Start BEFORE transport selection (the shm
    wire pre-creates its channel segments). The explicit
    ``-mv_engine_shards`` value; clamping modes (sync/elastic) and the
    multi-process auto default want one."""
    try:
        flag = int(GetFlag("mv_engine_shards"))
    except Exception:
        flag = 0
    if flag <= 1 or bool(GetFlag("sync")):
        return 1
    try:
        if bool(GetFlag("mv_elastic")):
            return 1
    except Exception:
        pass
    return flag


def engine_shard_cap() -> int:
    """Resolved engine shard-slot count for a NEW engine (see the
    ``-mv_engine_shards`` help text). The reference's actor runtime
    gives EVERY actor its own thread + mailbox (PAPER.md L1 — nothing
    forces one server actor); the clamps below are where this build's
    collective protocols genuinely do:

    * BSP (-sync): the vector clocks count verbs across all tables;
    * elastic epochs: the coordinator relay is one ordered channel;
    * multi-process on gloo: ONE globally-ordered collective stream —
      per-shard streams need a multi-channel wire's channels
      (-mv_wire: shm same-host, tcp cross-host)."""
    try:
        flag = int(GetFlag("mv_engine_shards"))
    except Exception:
        flag = 0
    if bool(GetFlag("sync")):
        return 1
    try:
        if bool(GetFlag("mv_elastic")):
            if flag > 1:
                Log.Info("engine: -mv_engine_shards=%d clamped to 1 "
                         "under -mv_elastic (the epoch relay is a "
                         "single ordered channel)", flag)
            return 1
    except Exception:
        pass
    if multihost.world_size() > 1:
        if flag <= 1:
            return 1        # auto: multi-process worlds opt in explicitly
        channels = multihost.wire_channels()
        if channels < flag:
            Log.Error("engine: -mv_engine_shards=%d needs %d "
                      "independent exchange channels but the active "
                      "wire offers %d (gloo is one ordered collective "
                      "stream — same-host worlds take -mv_wire=auto/"
                      "shm, cross-host worlds -mv_wire=tcp) — clamped "
                      "to 1", flag, flag, channels)
            return 1
        return flag
    if flag >= 1:
        return flag
    # auto, single-process: min(tables, cores/4) — the table bound
    # falls out of LAZY shard spawn (ShardedServer.RegisterTable)
    import os
    return max(1, min(8, (os.cpu_count() or 4) // 4))


#: non-verb message types the sharded router turns into CROSS-STREAM
#: CUTS (every shard fences at one agreed stream position, the payload
#: runs once, every shard releases): checkpoint/StoreLoad, serving
#: publish, the barrier drain ping, and FinishTrain. Any OTHER
#: non-verb type dispatches on shard 0 only (unknown types have no
#: cross-shard ordering to preserve).
def _fail_multi_members(env: Message) -> None:
    """on_reply of a Request_MultiVerb envelope: the ONLY reply an
    envelope ever takes is a failure sweep (actor poison via
    _fail_pending, or _dispatch's error routing when expansion itself
    raised) — forward it to every member so batch waiters raise typed
    instead of hanging on a dead engine. First-reply-wins on each
    member makes the forward idempotent against normal replies."""
    if isinstance(env.result, Exception):
        for m in env.payload.get("members", ()):
            m.reply(env.result)


_CUT_TYPES = (MsgType.Request_StoreLoad, MsgType.Request_Publish,
              MsgType.Request_Barrier, MsgType.Server_Finish_Train)


class _CutFence:
    """One cross-stream cut rendezvous (round 12).

    Every sub-shard's stream carries a fence message at the cut's
    position; its dispatch parks the shard here (``hold``). The head
    shard (the router, = shard 0) waits for every sub to arrive
    (``arrive_head``), runs the cut payload with ALL streams fenced —
    every verb admitted before the cut applied, none after, on every
    shard — then ``release``s the subs. All waits are poll-sliced and
    honour ``-mv_deadline_s``; a poisoned shard converts the wait into
    the typed ActorDied instead of a hang."""

    _POLL_S = 0.05

    def __init__(self, head: "Server", n_subs: int):
        self._head = head
        self._need = n_subs
        self._cv = threading.Condition()
        self._arrived = 0
        self._released = False
        self._abort: Optional[BaseException] = None

    def hold(self) -> None:
        """Sub-shard side: arrive, then block until the head releases
        the cut (or aborts / dies / the deadline expires)."""
        deadline = fdeadline.timeout_or_none()
        t0 = _time.perf_counter()
        with self._cv:
            self._arrived += 1
            self._cv.notify_all()
            while not self._released and self._abort is None:
                head_poison = getattr(self._head, "_poison", None)
                if head_poison is not None:
                    from multiverso_tpu.failsafe.errors import ActorDied
                    raise ActorDied(self._head.name, head_poison)
                self._cv.wait(self._POLL_S)
                if (deadline is not None
                        and _time.perf_counter() - t0 > deadline):
                    fdeadline.raise_deadline(
                        "cross-stream cut (the head shard never ran "
                        "the cut payload)", fatal=True)
            if self._abort is not None:
                raise self._abort

    def arrive_head(self, subs) -> None:
        """Head side: block until every sub-shard fenced. A dead sub
        (or an expired deadline) aborts the cut on every waiter."""
        deadline = fdeadline.timeout_or_none()
        t0 = _time.perf_counter()
        with self._cv:
            while self._arrived < self._need:
                for sub in subs:
                    if sub._poison is not None:
                        from multiverso_tpu.failsafe.errors import \
                            ActorDied
                        exc = ActorDied(sub.name, sub._poison)
                        self._abort = exc
                        self._cv.notify_all()
                        raise exc
                self._cv.wait(self._POLL_S)
                if (deadline is not None
                        and _time.perf_counter() - t0 > deadline):
                    try:
                        fdeadline.raise_deadline(
                            "cross-stream cut (a shard never fenced)",
                            fatal=True)
                    except BaseException as exc:
                        self._abort = exc
                        self._cv.notify_all()
                        raise

    def release(self) -> None:
        with self._cv:
            self._released = True
            self._cv.notify_all()


class _EngineShard(Server):
    """Sub-shard k of a :class:`ShardedServer`: a full engine actor —
    own thread, mailbox, window stream, exchange stage, SEQ counter,
    dedup window — whose ``store_`` is the SHARED table list and whose
    exchanges ride wire channel k (flight events stamped stream k).
    Non-verb messages only ever reach it as cut fences from the
    router."""

    def __init__(self, parent: "ShardedServer", slot: int):
        super().__init__(name=f"{actor_names.kServer}_shard{slot}")
        self.store_ = parent.store_     # ONE table list, router-owned
        self.mh_channel = slot
        self.mh_stream = slot
        for mt in _CUT_TYPES:
            self.RegisterHandler(mt, self._fence_entry)

    def _fence_entry(self, msg: Message) -> None:
        """Cut-fence dispatch: park this shard's stream until the head
        releases the cut. Failures reply typed (never hang the cut
        caller); a fatal abort (head death / deadline) re-raises so
        this shard poisons like any other desynced stream."""
        fence = (msg.payload or {}).get("_mv_fence")
        if fence is None:       # defensive: not a router fence
            msg.reply(None)
            return
        try:
            fence.hold()
        except Exception as exc:
            msg.reply(exc)
            if getattr(exc, "mv_fatal", False):
                raise
            return
        except BaseException as exc:
            # SystemExit & friends keep base-actor semantics: reply,
            # then let the escape kill + poison this shard's loop
            msg.reply(exc)
            raise
        msg.reply(None)


class ShardedServer(Server):
    """Round 12 — the sharded engine: this actor IS shard 0 and the
    router. Verbs route to a shard by ``table_id % shard_slots`` (rank-
    agreed arithmetic, so SPMD ranks agree on routing without
    negotiation) unless a ROUTING-MAP override is installed (round 20:
    the policy plane re-routes hot tables live via
    :meth:`install_routing`, at a fenced cross-stream cut so the change
    lands at one agreed position on every rank); each shard owns an
    independent window stream with
    its own exchange stage, SEQ counter and wire channel, so different
    tables' windows form, exchange and apply CONCURRENTLY — the fix
    for the flat ``host_scaling_Melem_s`` wall (ONE actor serialized
    every table). Sub-shards spawn LAZILY at table registration, so
    the effective shard count is min(tables, slots).

    Non-verb messages (checkpoint StoreLoad, serving Publish, barrier
    pings, FinishTrain) become CROSS-STREAM CUTS: every shard fences
    at the cut's position in ITS stream (in a multi-process world each
    fence is a barrier head-marker exchange on the shard's own
    channel, lockstep per shard by the SPMD contract), the payload
    runs ONCE with all streams fenced, then every shard releases. Every
    verb admitted before the cut is applied before the payload runs
    and none after — on every shard — which is exactly the PR 5
    publish-barrier soundness argument lifted to N streams (DESIGN.md
    §14)."""

    def __init__(self, shard_cap: int):
        super().__init__()
        CHECK(shard_cap >= 2,
              f"ShardedServer needs >= 2 shard slots, got {shard_cap}")
        self._shard_cap = shard_cap
        self._subs: Dict[int, _EngineShard] = {}
        #: round 20 — the table->shard ROUTING MAP: overrides on top of
        #: the ``table_id % shard_cap`` default. Installed ONLY inside
        #: a cross-stream cut payload (policy plane install_routing:
        #: every stream fenced, every pre-cut verb applied), so routing
        #: for a table changes at ONE agreed multi-stream position; in
        #: SPMD worlds the installing cut is issued at the same
        #: lockstep app position on every rank (the MV_PolicySync
        #: discipline), keeping the per-shard verb streams rank-agreed.
        self._routing: Dict[int, int] = {}
        #: routing-map installs applied (the /actions + drill probe)
        self.routing_installs = 0
        #: the ROUTING FREEZE (round 20 review fix): route-decision +
        #: mailbox-push must be atomic against cut-fence enqueue, or a
        #: verb that computed its slot under the OLD map could land
        #: BEHIND the fence in the old stream while the cut swaps the
        #: map — splitting one table's verbs across two concurrently
        #: draining streams (per-table serial order broken). Cuts
        #: close the gate (under _route_lock) before enqueueing their
        #: fences and reopen it when the LAST in-flight cut releases;
        #: verb pushes spin on the gate (bounded waits) and route
        #: under the same lock. The open-gate fast path costs one
        #: Event check + one uncontended lock per push.
        self._route_lock = threading.Lock()
        self._route_open = threading.Event()
        self._route_open.set()
        self._cuts_inflight = 0
        #: cross-stream cuts processed (the sharded sibling of
        #: window_barrier_splits, which counts shard 0's stream only)
        self.cut_count = 0
        for mt in _CUT_TYPES:
            self.RegisterHandler(mt, self._wrap_cut(self._handlers[mt]))

    def _slot_for(self, table_id: int) -> int:
        """Effective shard slot of ``table_id``: the routing-map
        override when one is installed, else the rank-agreed modulo
        default. One dict get on the verb path."""
        if table_id < 0:
            return 0
        slot = self._routing.get(table_id)
        return (table_id % self._shard_cap) if slot is None else slot

    def install_routing(self, mapping: Dict[int, int]) -> list:
        """Install table->shard overrides. MUST run as a cross-stream
        cut payload (Zoo.CallOnEngine): with every stream fenced, every
        verb admitted before the cut has applied under the OLD map and
        none after, so a table's window stream migrates between shard
        channels at one consistent position. Targets are restricted to
        LIVE slots (0 or a spawned sub-shard) and known tables; the
        returned ``[(table_id, prev_slot, new_slot), ...]`` names what
        actually changed (the policy plane's revert input). Idempotent:
        re-installing the current slot is a no-op entry."""
        live = {0} | set(self._subs)
        applied = []
        for tid, slot in sorted(mapping.items()):
            tid, slot = int(tid), int(slot)
            CHECK(0 <= tid < len(self.store_),
                  f"install_routing: unknown table {tid}")
            CHECK(slot in live,
                  f"install_routing: slot {slot} not live (live slots "
                  f"{sorted(live)})")
            prev = self._slot_for(tid)
            if prev == slot:
                continue
            self._routing[tid] = slot
            applied.append((tid, prev, slot))
        if applied:
            self.routing_installs += 1
        return applied

    def routing_report(self) -> dict:
        """Effective routing of every registered table + live slots
        (LOCAL probe — the policy decider's and /actions' input)."""
        return {"shard_cap": self._shard_cap,
                "live_slots": sorted({0} | set(self._subs)),
                "installs": self.routing_installs,
                "overrides": dict(self._routing),
                "routing": {tid: self._slot_for(tid)
                            for tid in range(len(self.store_))}}

    def _wrap_cut(self, base):
        def entry(msg: Message) -> None:
            fence = getattr(msg, "_mv_cut", None)
            if fence is None:       # no subs were live at routing time
                return base(msg)
            try:
                fence.arrive_head(list(self._subs.values()))
                base(msg)
            finally:
                # release + reopen even when the rendezvous aborted (a
                # dead sub / expired deadline): a stuck freeze would
                # park every verb push forever
                fence.release()
                self._cut_done()
        return entry

    def _cut_done(self) -> None:
        """One in-flight cut finished: reopen the routing gate when it
        was the last (cuts may overlap — publish racing a policy
        install — and the gate must stay closed until ALL fences are
        resolved)."""
        with self._route_lock:
            self._cuts_inflight -= 1
            if self._cuts_inflight <= 0:
                self._cuts_inflight = 0
                self._route_open.set()

    def _route_push(self, msg: Message) -> None:
        """Route one verb and push it to its stream, atomically
        against cut-fence enqueue (see the routing-freeze note in
        __init__). The open-gate path is one Event check + one
        uncontended lock."""
        while True:
            opened = self._route_open.wait(0.5)
            if not opened and self._poison is not None:
                # router died mid-cut and the gate will never reopen:
                # fall through — the push surfaces the typed ActorDied
                # instead of spinning forever
                pass
            elif not opened:
                continue
            with self._route_lock:
                if (self._route_open.is_set()
                        or self._poison is not None):
                    sub = self._subs.get(self._slot_for(msg.table_id))
                    if sub is not None:
                        # mv-lint: ok(lock-order): sub is an _EngineShard whose Receive IS Actor.Receive (mailbox push, no _route_lock) — the by-name edge to ShardedServer.Receive cannot execute (a sub is never the router)
                        sub.Receive(msg)    # chaos/poison apply there
                    else:
                        super().Receive(msg)
                    return

    def RegisterTable(self, server_table) -> int:
        table_id = super().RegisterTable(server_table)
        if multihost.world_size() > 1:
            # pre-warm a KV table's host values at THIS lockstep
            # position: a multi-stream engine cannot order collective
            # applies, so the bootstrap the single engine did in the
            # first fenced window must happen here instead
            # (tables/base.py mh_prepare_local_apply contract)
            try:
                server_table.mh_prepare_local_apply()
            except Exception as exc:
                Log.Error("engine: table %d local-apply pre-warm "
                          "failed (%r) — its first window will need a "
                          "collective apply", table_id, exc)
        slot = table_id % self._shard_cap
        if slot and slot not in self._subs:
            sub = _EngineShard(self, slot)
            self._subs[slot] = sub
            if multihost.world_size() > 1:
                # N live streams in a multi-process world: no shard may
                # issue collective APPLIES any more (loud CHECK in
                # _mh_fence_cause; cut payloads stay exempt — every
                # stream is fenced while they run)
                self.mh_single_collective_stream = False
                sub.mh_single_collective_stream = False
                for other in self._subs.values():
                    other.mh_single_collective_stream = False
            sub.Start()
            Log.Debug("engine: shard %d spawned (table %d; %d/%d "
                      "slots live)", slot, table_id,
                      1 + len(self._subs), self._shard_cap)
        return table_id

    def receive_multi(self, members) -> None:
        """Split one batch per shard stream (round 19): routing is by
        table (``table_id % slots``), so splitting the member list by
        slot preserves every TABLE's submission order — the guarantee
        the batched-verb contract makes — while each shard still takes
        its sub-batch as one envelope. Worst case the batch costs
        min(len, live shards) pushes instead of one; per-shard verb
        positions stay lockstep across SPMD ranks because the split is
        the same rank-agreed arithmetic the router uses."""
        if not self._subs:
            return super().receive_multi(members)
        # route + push under the routing-freeze gate, like every other
        # verb path (the slot decisions and the pushes must be one
        # atomic step against a cut's fence enqueue)
        while True:
            opened = self._route_open.wait(0.5)
            if not opened and self._poison is None:
                continue
            with self._route_lock:
                if (not self._route_open.is_set()
                        and self._poison is None):
                    continue
                groups: Dict[int, list] = {}
                for m in members:
                    groups.setdefault(self._slot_for(m.table_id),
                                      []).append(m)
                for slot, ms in groups.items():
                    sub = self._subs.get(slot)
                    if sub is not None:
                        sub.receive_multi(ms)
                    else:
                        Server.receive_multi(self, ms)
                return

    def Receive(self, msg: Message) -> None:
        if msg.msg_type is MsgType.Request_MultiVerb:
            # a pre-wrapped envelope (tests / direct callers): re-split
            # it per shard — letting shard 0 expand it would put other
            # shards' tables into the wrong window stream
            self.receive_multi(msg.payload["members"])
            return
        if msg.msg_type in (MsgType.Request_Get, MsgType.Request_Add):
            self._route_push(msg)
            return
        subs = list(self._subs.values())
        if not subs or msg.msg_type not in _CUT_TYPES:
            super().Receive(msg)
            return
        # CROSS-STREAM CUT: fence every sub-shard's stream, then send
        # the head message to shard 0. Per-shard mailbox order is the
        # caller's program order restricted to that shard, so SPMD
        # ranks place every fence at the same per-shard stream
        # position — the cut is one agreed multi-stream position. The
        # fences enqueue with the ROUTING GATE closed: a concurrent
        # verb either pushed before them (ahead of the fence — applied
        # under the pre-cut routing before any payload runs) or routes
        # after the cut fully releases (under whatever map the payload
        # installed) — never with an old decision behind the fence.
        self.cut_count += 1  # mv-lint: ok(cross-domain-state): diagnostics-only tally; worker cuts and the policy thread's installs may race the GIL int add and at worst under-count a probe nothing gates on
        fence = _CutFence(self, len(subs))
        with self._route_lock:
            self._cuts_inflight += 1
            self._route_open.clear()
            for sub in subs:
                sub.Receive(Message(msg_type=msg.msg_type,
                                    payload={"_mv_fence": fence}))
            msg._mv_cut = fence
            super().Receive(msg)

    # -- facade points -------------------------------------------------------

    def epoch_for_table(self, table_id: int) -> int:
        sub = self._subs.get(self._slot_for(table_id))
        return (sub or self).window_epoch

    def cut_epoch(self) -> int:
        return self.window_epoch + sum(s.window_epoch
                                       for s in self._subs.values())

    def shard_states(self) -> List[dict]:
        out = super().shard_states()
        for slot in sorted(self._subs):
            out.extend(self._subs[slot].shard_states())
        return out

    def Stop(self) -> None:
        # shard 0 (the router) first: its drain may still dispatch a
        # queued cut, which needs the subs alive to fence; the subs'
        # own drains then flush any released fences
        super().Stop()
        for sub in self._subs.values():
            sub.Stop()


class SyncServer(Server):
    """BSP server (reference server.cpp:60-222). See module docstring.

    The vector clocks count single Get and Add messages, and they
    judge every message by itself and in mailbox order: defer it into
    ``_add_cache`` / ``_get_cache``, or admit it and tick, and where a
    tick completes a round, drain the cache at that position. The
    device work of what they admit is not done on the spot. In a
    single process the engine takes what the mailbox holds as
    ``Server`` does (``_take_window``) and serves it through the same
    window loop (``_local_window``); what the class decides is how the
    verbs are cut into stretches (``_cut``) and what a window takes on
    top (``_take_late``):

    * a maximal stretch of consecutive admitted Adds of one table is ONE
      ``_process_add_run``. Where its two or more payloads name the
      same rows (the workers of a synchronous round push one shared
      set) the table sums them on the host and applies ONE lone Add
      (``_offer_add_run``, ``ProcessAddSameRows``: one dispatch, a
      quarter of the bytes into the device for a stretch of four, the
      lone Add's own program); any other stretch goes verb by verb (see
      there why the stacked run stays out of BSP). Every Add is in the
      table when its reply goes out, summed or lone;
    * a maximal stretch of consecutive admitted Gets is dispatched
      together (``ProcessGetAsync``) and finalized together, identical
      Gets sharing one gather and one copy back, whoever sent them;
    * THE ORDERING RULE, which is not ``Server``'s (that one moves every
      Add of a table to the position of the table's first: "a Get may
      observe more progress, never less"; under BSP worker 0's Add of
      round r+1 can sit in one batch behind the others' Gets of round
      r): no Add is applied ahead of a Get that the clocks placed
      before it, and no Get is dispatched ahead of an Add placed before
      it. A stretch never reaches across a verb of the other kind, a
      verb of another table ends an Add stretch, and a non-verb message
      (FinishTrain, StoreLoad, ...) ends every stretch and runs at its
      own position. A Get dispatched before a later Add stretch and
      finalized after it reads the earlier state (the gather's output
      is a fresh buffer), and sharing one gather among the Gets of a
      stretch is exact: no Add lies between them;
    * a drain serves its cached verbs as stretches at the tick's
      position, under its ``server.bsp.drain`` span: its Adds applied,
      its Gets dispatched among the window's own and answered with them
      (a held Get shares the gather of the round's Gets behind it);
    * before a window copies its Gets back it takes what has landed in
      the mailbox since (``_take_late``), and the clocks judge and the
      window serves that as its next messages: the Get of a worker that
      was answered a moment later than the others joins their gather
      while it is in flight. Nothing is waited for: what decides is
      what the mailbox holds when the engine looks. The answers of a
      shared gather go out together, so the workers' next sends land
      together: a world in lock step settles at one gather a round.

    What still differs from the windowed engine: one engine shard
    whatever ``-mv_engine_shards`` says (``engine_shard_cap``: the
    clocks span all tables); no worker-side write combining, no Get
    cache, no batched envelopes from the zoo (the three class flags
    below); and with more than one process a window of ONE verb, as
    every verb there is a host collective that all ranks must issue in
    one order, which a window cut by each rank's own mailbox race is
    not.

    A Get that arrives before its round's last Add waits in
    ``_get_cache`` and is served by the drain that Add sets off; an Add
    of a worker whose Get ran ahead of the get round waits in
    ``_add_cache``. The benchmark's cell ``mt_bsp_rounds``
    (``benchmark/runners/table_bsp_rounds.py``, PERF.md sections 4 to 6)
    measures this path on the chip; its per-layer metrics read the
    ``server.bsp.*`` spans and instruments below."""

    #: the vector-clock protocol counts Get/Add MESSAGES per worker:
    #: worker-side write combining / get caching would break the round
    #: accounting ("all workers issue the same number of Gets/Adds")
    GET_CACHE_OK = False
    WRITE_COMBINE_OK = False
    #: ...and batched envelopes would hide N clock ticks inside one
    #: message — Zoo.SendToServerMulti delivers members individually.
    #: (An envelope a direct caller lands all the same is flattened at
    #: its mailbox position by the inherited window entry: each member
    #: is judged by the clocks as a message of its own.)
    MULTI_VERB_OK = False
    #: rounds whose first Add has been admitted and whose last Get has
    #: not been answered, at most (``server.bsp.round_s``): workers that
    #: alternate Add and Get keep one or two open; a world that only
    #: Adds would otherwise grow the record for ever
    _ROUNDS_OPEN_MAX = 64

    def __init__(self, num_workers: int):
        super().__init__()
        self._num_workers = num_workers
        self._get_clocks = VectorClock(num_workers)
        self._add_clocks = VectorClock(num_workers)
        self._num_waited_add = [0] * num_workers
        self._add_cache: Deque[Message] = collections.deque()
        self._get_cache: Deque[Message] = collections.deque()
        #: telemetry: worst clock skew across both vector clocks — how
        #: stale the slowest worker's view is vs the fastest's. A
        #: MAX-merge gauge: the job-wide number is the worst rank's
        #: skew, not a sum over ranks
        self._t_staleness = tmetrics.max_gauge("server.bsp.staleness")
        #: telemetry of the rounds, all on this thread: add rounds
        #: completed; Gets and Adds the clocks saw, and those of them a
        #: cache held first; a round's length, from the admission of its
        #: first Add to the reply of the Get that completes the next get
        #: round (sum and count exact, as server.window.latency_s)
        self._t_rounds = tmetrics.counter("server.bsp.rounds")
        self._t_gets = tmetrics.counter("server.bsp.gets")
        self._t_gets_cached = tmetrics.counter("server.bsp.gets_cached")
        self._t_adds = tmetrics.counter("server.bsp.adds")
        self._t_adds_cached = tmetrics.counter("server.bsp.adds_cached")
        self._t_round_s = tmetrics.histogram("server.bsp.round_s")
        #: Adds each worker has sent: the round its next Add belongs to
        self._adds_seen = [0] * num_workers
        self._rounds_begun = 0
        #: when the first Add of each open round was admitted, oldest
        #: first; the get round that completes closes the oldest
        self._round_t0: Deque[float] = collections.deque(
            maxlen=self._ROUNDS_OPEN_MAX)
        #: the open ``server.bsp.get_hold`` span of each cached Get, in
        #: the get cache's order (the shared no-op while -trace is off)
        self._get_holds: Deque = collections.deque()

    def _note_staleness(self) -> None:
        self._t_staleness.set(max(self._get_clocks.staleness(),
                                  self._add_clocks.staleness()))

    def _get_entry(self, msg: Message) -> None:
        """Window handler of Gets and Adds (and of an envelope a direct
        caller landed). The failsafe admission gate (dedup + chaos)
        applies BEFORE the clocks see a verb: a duplicate Add must not
        tick a vector clock twice. Multi-process: a window of one (see
        the class docstring)."""
        cap = 1 if multihost.world_size() > 1 else self.GET_PIPELINE_WINDOW
        batch = self._take_window(msg, cap)
        if batch:
            self._run_window(batch)

    def _take_late(self, taken: int) -> list:
        """What has landed since the window was taken (single process;
        the window's cap holds for the whole of it). No waiting: a
        worker whose verb is not there yet opens the next window."""
        if multihost.world_size() > 1:
            return []
        return self._take_window(None, self.GET_PIPELINE_WINDOW - taken)

    def _cut(self, verbs):
        """The BSP cut: the clocks judge every verb in mailbox order
        (``_judge_add`` / ``_judge_get``, reference server.cpp:139-186)
        and what they admit is appended to the stretches in the order
        they admit it: a deferred verb appears where its drain puts it.
        So stretches hold the ordering rule of the class docstring by
        construction: an admitted verb joins the stretch that ends the
        list only if that holds verbs of its kind (and, for Adds, of its
        table). Every Get is keyed: one that lands late may equal one in
        flight."""
        out: list = []
        for m in verbs:
            if m.msg_type is MsgType.Request_Add:
                self._judge_add(m, out)
            else:
                self._judge_get(m, out)
        return out, True

    @staticmethod
    def _get_dedup_key(m: Message):
        """All workers' Gets of a round are equal (the guarantee), so
        who asks, which is all a ``GetOption`` holds, is no part of a
        Get's identity: Gets that name the same rows share one gather
        whoever sent them. (A table whose answer does depend on the
        asker, SparseMatrixTable, has no two-phase Get and shares
        nothing.) A Get answered from another's gather does not pass
        through the table: what the table notes a Get (the row-access
        sketch, ``_note_row_access``) counts a shared gather once, as
        under ``Server``'s dedup."""
        return Server._get_dedup_key(m, skip=GetOption)

    def _judge_add(self, msg: Message, out: list) -> None:
        worker = msg.src
        self._t_adds.inc()
        sent = self._adds_seen[worker]
        self._adds_seen[worker] = sent + 1
        if sent >= self._rounds_begun:      # the first Add of a round
            self._rounds_begun = sent + 1
            self._round_t0.append(_time.perf_counter())
        # 1. Before add: cache faster worker (server.cpp:141-147)
        if self._get_clocks.local_clock(worker) > self._get_clocks.global_clock():
            self._add_cache.append(msg)
            self._num_waited_add[worker] += 1
            self._t_adds_cached.inc()
            self._note_staleness()
            return
        # 2. Process add: its place among the stretches
        _extend_stretch(out, _ADDS, msg)
        # 3. After add: drain cached gets when the add round completes
        if self._add_clocks.Update(worker):
            self._t_rounds.inc()
            CHECK(not self._add_cache, "add cache must be empty at round end")
            out += self._drain_gets()
        self._note_staleness()

    def _judge_get(self, msg: Message, out: list) -> None:
        worker = msg.src
        self._t_gets.inc()
        # 1. Before get: wait for other workers' adds (server.cpp:164-171)
        if (self._add_clocks.local_clock(worker) > self._add_clocks.global_clock()
                or self._num_waited_add[worker] > 0):
            self._get_cache.append(msg)
            self._get_holds.append(ttrace.begin("server.bsp.get_hold",
                                                cat="server"))
            self._t_gets_cached.inc()
            self._note_staleness()
            return
        # 2. Process get: its place among the stretches
        _extend_stretch(out, _GETS, msg)
        # 3. After get: drain cached adds when the get round completes
        if self._get_clocks.Update(worker):
            if self._round_t0:
                self._observe_round_at_reply(msg, self._round_t0.popleft())
            out += self._drain_adds()
        self._note_staleness()

    def _observe_round_at_reply(self, msg: Message, t0: float) -> None:
        """``server.bsp.round_s`` ends where the worker is answered, not
        where the clock ticked: shadow ``msg.reply`` (as
        ``_fs_wrap_reply`` does) so the round is observed behind the
        finalize that replies."""
        orig = msg.reply

        def _reply(result=None):
            orig(result)
            self._t_round_s.observe(_time.perf_counter() - t0)

        msg.reply = _reply

    def _offer_add_run(self, table, payloads) -> bool:
        """A stretch of two or more Adds is offered to the table's
        same-rows run and to nothing else (``ProcessAddSameRows``):
        where every payload names the same id array, as the workers of
        a synchronous round do, the deltas are summed on the host in
        message order and applied as ONE lone Add, one dispatch and one
        acknowledgement of every message after it. A round's Adds are
        bound by their bytes into the device, and those fall with the
        count of Adds summed; the program is the lone Add's, so there
        is nothing to warm and no count that races the workers' sends;
        and a stretch of two is worth as much an Add as one of four, so
        nothing is waited for. All or nothing a stretch: one that mixes
        id sets, a non-linear updater, a compressed or whole-table Add
        decline and the stretch goes verb by verb. Never the STACKED
        run of ``ProcessAddRun``: that is a program a count of Adds and
        of distinct rows, which a world meets races its workers' sends,
        so none can be brought up before a timed stretch of rounds, and
        it carries every Add's bytes into the device all the same
        (PERF.md section 6, PR 51 and PR 53)."""
        return table.ProcessAddSameRows(payloads)

    def _drain_gets(self) -> list:
        """The cached Gets, ticked: the round's last Add has landed (or
        the last worker still adding has finished training). -> what
        serves them, to run behind that Add (nothing for an empty
        cache)."""
        if not self._get_cache:
            return []
        msgs, holds = list(self._get_cache), list(self._get_holds)
        self._get_cache.clear()
        self._get_holds.clear()
        for m in msgs:
            CHECK(not self._get_clocks.Update(m.src),
                  "drained Get must not complete a round")
        return [functools.partial(self._serve_drain, [(_GETS, msgs)], holds)]

    def _drain_adds(self) -> list:
        """The cached Adds, ticked: the get round they ran ahead of is
        complete. -> what applies them (nothing for an empty cache)."""
        if not self._add_cache:
            return []
        stretches: list = []
        for m in self._add_cache:
            _extend_stretch(stretches, _ADDS, m)
            CHECK(not self._add_clocks.Update(m.src),
                  "drained Add must not complete a round")
            self._num_waited_add[m.src] -= 1
        self._add_cache.clear()
        return [functools.partial(self._serve_drain, stretches, ())]

    def _serve_drain(self, stretches, holds, pending: list,
                     seen: dict) -> None:
        """Serve a drain's stretches at the tick's position: its Adds
        applied, its Gets dispatched among the window's own (``pending``),
        to be copied back and answered with them: a held Get and the
        round's Gets behind it share one gather."""
        with ttrace.span("server.bsp.drain", cat="server"):
            for hold in holds:
                hold.end()
            self._serve(stretches, True, pending, seen, None)

    def ProcessFinishTrain(self, msg: Message) -> None:
        """server.cpp:188-211: force worker clocks to infinity, drain caches."""
        worker = msg.src
        drains: list = []
        if self._add_clocks.FinishTrain(worker):
            CHECK(not self._add_cache, "add cache must be empty")
            drains += self._drain_gets()
        if self._get_clocks.FinishTrain(worker):
            CHECK(not self._get_cache, "get cache must be empty")
            drains += self._drain_adds()
        pending: list = []
        for drain in drains:
            drain(pending, {})
        self._finalize(pending, None)
        msg.reply(None)
