"""Table interfaces: worker side (async handles) and server side (sharded
HBM store + jit'd updater application).

Behavioral equivalent of reference include/multiverso/table_interface.h and
src/table.cpp:

* ``WorkerTable`` — allocates per-request msg ids, keeps a Waiter per
  in-flight request, offers sync ``Get/Add`` = ``Wait(GetAsync/AddAsync)``
  (table.cpp:25-39), and ``Wait/Notify/Reset`` bookkeeping
  (table.cpp:84-110).

* ``ServerTable`` — ``ProcessAdd``/``ProcessGet`` virtuals plus the
  ``Serializable`` Store/Load checkpoint contract (table_interface.h:61-79).

TPU design: requests are routed to the single server engine actor which
serializes application onto the mesh-sharded store (see sync/server.py).
The async handle's value: ``AddAsync`` returns after *enqueueing* — the
jit'd shard update is dispatched by the server thread and XLA executes it
asynchronously, so worker threads overlap data prep with device work, which
is the reference's pipeline idiom (ps_model.cpp:228-259) for free.

``CreateTable`` mirrors table_factory (reference table_factory.h:16-27):
builds the server half, registers it with the engine, builds the worker
half bound to the same table id.
"""

from __future__ import annotations

import random
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, Optional

import numpy as np

from multiverso_tpu.failsafe import deadline as fdeadline
from multiverso_tpu.failsafe.errors import TransientError
from multiverso_tpu.message import (Message, MsgType, copy_result,
                                    next_msg_id, own_result)
from multiverso_tpu.parallel.wire import payload_nbytes
from multiverso_tpu.telemetry import metrics as tmetrics
from multiverso_tpu.telemetry import trace as ttrace
from multiverso_tpu.updaters.base import AddOption, GetOption
from multiverso_tpu.utils.configure import cached_int_flag
from multiverso_tpu.utils.dashboard import monitor_region
from multiverso_tpu.utils.log import CHECK, Log
from multiverso_tpu.utils.waiter import Waiter

#: retry backoff: base * 2**attempt plus uniform jitter of one base —
#: small absolute values (transients here are engine-injected or
#: momentary, not WAN outages) so tests and tight loops stay fast
_RETRY_BACKOFF_BASE_S = 0.02

#: listener-refreshed cache (Wait runs once per tracked verb — no
#: GetFlag registry walk on that path); flag defined in failsafe.deadline
_max_retries_flag = cached_int_flag("mv_max_retries", 3)

#: round 7 worker-side fast paths; flags DEFINED in sync/server.py (the
#: eagerly-imported flag home) and read here through listener caches
_write_combine_flag = cached_int_flag("mv_write_combine", 8)
_get_staleness_flag = cached_int_flag("mv_get_staleness", 0)

#: bound on the staleness-bounded Get cache: distinct request keys kept
#: per table (repeated training loops reuse a handful of request
#: shapes; an unbounded key set would pin every result ever fetched)
_GET_CACHE_ENTRIES = 64


def _result_nbytes(result) -> int:
    """Host bytes a fetched result pins (accounting ledger): arrays by
    ``nbytes``, one container level deep — the shapes copy_result
    handles. Non-array scalars count as zero (noise)."""
    if isinstance(result, np.ndarray):
        return int(result.nbytes)
    if isinstance(result, (tuple, list)):
        return sum(_result_nbytes(r) for r in result)
    if isinstance(result, dict):
        return sum(_result_nbytes(r) for r in result.values())
    return 0


@dataclass
class TableOption:
    """Base table creation record (reference CreateTableOption structs)."""

    dtype: Any = np.float32
    #: opt-in wire compression for row Adds across the host<->device
    #: boundary: "sparse" (exact — (index, value) pairs when >half the
    #: payload is zero, dense fallback otherwise; reference
    #: quantization_util.h:95-137) or "1bit" (lossy — sign bits + two
    #: means with per-row error feedback). Decompression happens in the
    #: jit'd consumer ON DEVICE, so the saved bytes are real transfer
    #: bytes. None = off. Tables that don't implement a compressed wire
    #: leave _supports_compress False — CreateTable rejects the request
    #: loudly instead of silently shipping dense.
    compress: Any = None
    _supports_compress = False


class ServerTable:
    """Server half: owns the sharded device store (table_interface.h:61-79)."""

    #: replica-plane publish journal (round 17,
    #: multiverso_tpu/replica/delta.py): attached at RegisterTable when
    #: the fan-out plane owns this rank, None otherwise (one attribute
    #: read on every apply). CONTRACT: every APPLIED Add marks it —
    #: matrix families through the ``_note_add_parts`` hook (fires
    #: after the data update on every Add path, so a rejected add never
    #: dirties the journal), kv through ``_apply_merged_kv``, array at
    #: its apply sites. ``publish_journal_kind`` picks the granularity:
    #: "rows" (row bitmap — the SparseMatrixTable up_to_date idiom),
    #: "keys" (write-set of touched keys), "all" (whole-table flag).
    _pub_journal = None
    publish_journal_kind = "all"

    def ProcessAdd(self, **payload) -> None:
        raise NotImplementedError

    def ProcessGet(self, **payload) -> Any:
        raise NotImplementedError

    def ProcessGetAsync(self, **payload):
        """Two-phase Get for RTT pipelining: dispatch the device program
        AND start the device->host copy, return a zero-arg finalize
        callable producing the result — or None when this table (or this
        payload) can't split the phases, in which case the engine falls
        back to the blocking ProcessGet. The async Server engine drains a
        window of queued Gets through the dispatch phase first, so their
        host copies overlap instead of serializing one RTT per Get (the
        reference's C++ server was memcpy-bound, not RTT-bound; a remote
        accelerator makes the copy the cost to hide)."""
        return None

    def ProcessAddRun(self, payloads) -> bool:
        """Engine add-coalescing hook: apply a window's queued Adds to
        this table as ONE merged dispatch. Return True when handled;
        False declines (the engine then processes each Add normally —
        the path that produces precise per-message errors). CONTRACT:
        validate everything BEFORE mutating state — an exception from
        this method fails the whole run, with no per-message fallback."""
        return False

    def ProcessAddSameRows(self, payloads) -> bool:
        """The same-rows run: apply a stretch of two or more queued Adds
        whose payloads all name the SAME rows as ONE lone Add of their
        host-side sum, and return True; False declines (the default),
        and the engine then goes on as if it had not asked. It is what
        the BSP engine offers a stretch of Adds (``SyncServer``: the
        workers of a round push the same rows, and a sum needs no
        program of its own, where a stacked run is a program a count of
        Adds that no world can warm before it meets it), and what a
        table's ``ProcessAddRun`` may try first. A table accepts only
        where summing first equals applying one by one (a linear
        updater: ``update(update(s, a), b) == update(s, a + b)``) and
        never writes to a payload's arrays. The CONTRACT is
        ``ProcessAddRun``'s: all or nothing a stretch, everything
        validated BEFORE state mutates (an exception fails every Add of
        the stretch), and what a table notes an Add (freshness bits, the
        publish journal) is noted once a payload, in message order."""
        return False

    # -- multi-process WINDOW protocol hooks (sync/server.py windowed
    # engine, round 5): the engine exchanges a whole window of verbs in
    # ONE host collective and hands every rank's payloads down, so table
    # code on every rank sees identical merged data and must NOT issue
    # its own host collectives inside these hooks (device programs —
    # shard_map/psum over the global mesh — are fine and expected).
    # DETERMINISM CONTRACT: given identical ``parts``, every rank must
    # make identical mutate-or-raise decisions, or replicated/sharded
    # state diverges. The defaults fall back to the table's own
    # single-verb processing of THIS rank's payload — safe for custom
    # tables because the engine calls the hooks in lockstep positions,
    # so any collectives such a table issues internally still match.

    def ProcessAddParts(self, parts, my_rank: int) -> None:
        """Apply ONE logical collective Add given every rank's payload
        dict in rank order (``parts[my_rank]`` is this rank's own)."""
        self.ProcessAdd(**parts[my_rank])

    @staticmethod
    def _norm_parts_options(parts) -> list:
        """Every rank's Add option in rank order, ``None`` normalized to
        the default: cross-rank agreement must compare SEMANTICS — a
        rank that spelled the default as None is not divergent."""
        return [p.get("option") or AddOption() for p in parts]

    @classmethod
    def _check_parts_options(cls, parts) -> list:
        """Normalized options, CHECK-failing the world when ranks truly
        diverge (the SPMD collective contract). Sites that prefer to
        decline a merge instead use _norm_parts_options directly."""
        opts = cls._norm_parts_options(parts)
        CHECK(all(o == opts[0] for o in opts),
              f"collective Add options diverge across processes: {opts}")
        return opts

    def ProcessGetParts(self, parts, my_rank: int):
        """Serve ONE logical collective Get for THIS rank given every
        rank's payload dict in rank order; returns this rank's result."""
        return self.ProcessGet(**parts[my_rank])

    def ProcessAddRunParts(self, positions, my_rank: int) -> bool:
        """Cross-rank add-coalescing: ``positions`` is a list over window
        positions of per-rank payload-dict lists (one logical collective
        Add each). Apply them ALL as merged dispatch(es) and return True,
        or False to decline (the engine then runs ProcessAddParts per
        position). Same validate-before-mutate contract as
        ProcessAddRun."""
        return False

    def ProcessGetWindowParts(self, positions, my_rank: int):
        """Cross-rank get-dedup: serve a window segment's Gets to this
        table in one shot. ``positions`` is a list over window positions
        of per-rank payload-dict lists. Return a list of this rank's
        results (one per position; an Exception entry fails that
        position's request only), or None to decline (per-position
        ProcessGetParts then runs)."""
        return None

    def mh_prepare_local_apply(self) -> None:
        """Round 12 — called at table REGISTRATION in sharded
        multi-process worlds (sync/server.py ShardedServer), a
        lockstep program position BEFORE any verb reaches the table's
        shard stream: eagerly create whatever host copy makes
        :meth:`mh_apply_is_local` true (a KV table's host values), so
        the table's very first window is already host-local. A
        multi-stream engine cannot order collective applies across its
        live streams, so a nonlocal window there CHECK-fails loudly
        (_mh_fence_cause) — without this hook the window that creates
        the copy (the single-engine design lets the FIRST fenced window
        create it) would be that nonlocal window. Collective reads are
        safe here: every rank registers the table at the same program
        position. Default no-op: the table then stays nonlocal and
        the CHECK's advice applies (every Matrix / SparseMatrix table,
        on every backend)."""

    def mh_apply_is_local(self) -> bool:
        """True when EVERY windowed-engine apply/serve path of this
        table for already-exchanged parts runs entirely on the host —
        no collective device programs. The pipelined engine (round 7,
        sync/server.py) overlaps window N's apply with window N+1's
        host exchange only for all-local windows: an apply-side device
        collective racing the exchange thread's allgather could
        interleave in a different order on different ranks and deadlock
        the world.

        CONTRACT: the answer must be rank-agreed — derive it only from
        creation-time-agreed configuration and state that evolves at
        lockstep verb positions (e.g. a KV table's replicated host
        values, created by the first host verb on every rank), never from
        per-rank racy conditions. False is always safe (the engine then
        fences the window, exactly the serial schedule)."""
        return False

    # -- DEVICE-wire transport hooks (round 6; sync/server.py adaptive
    # transport). When the engine selects the device wire for an Add
    # (-window_transport, payload-size auto rule), the window exchange
    # ships only the values' dtype/shape metadata (wire.DeferredArray)
    # and the bytes move through the table's own device-parts
    # collectives — on a pod that is ICI at fabric bandwidth instead of
    # the host staging allgather. A table opts in per payload via
    # device_wire_add_ok; the engine then routes the position through
    # ProcessAddPartsDevice on EVERY rank (the deferred flag is visible
    # in the exchanged metadata, so the decision is lockstep).

    def device_wire_add_ok(self, payload) -> bool:
        """True when this table can apply ``payload`` as a collective
        Add whose ``values`` bytes never cross the host wire. Default
        False — the engine never defers for tables that don't opt in,
        so ProcessAddPartsDevice stays unreachable for them."""
        return False

    def ProcessAddPartsDevice(self, parts, my_rank: int) -> None:
        """Apply ONE logical collective Add whose values ride the
        device wire: ``parts`` is every rank's payload dict in rank
        order, where deferred values are wire.DeferredArray placeholders
        (this rank's placeholder carries the real array in ``.local``).
        Must run a COLLECTIVE device program (every rank participates)
        and must not issue host collectives. Only reachable after
        device_wire_add_ok accepted the payload at pack time."""
        raise NotImplementedError(
            "device-wire Add routed to a table without "
            "ProcessAddPartsDevice (device_wire_add_ok must stay False "
            "for such tables)")

    def ProcessAddRunPartsDevice(self, positions, my_rank: int) -> bool:
        """Merged device-wire run: apply a window's deferred collective
        Adds (``positions`` is a list over window positions of per-rank
        payload dicts whose values may be wire.DeferredArray) in ONE
        collective device round and return True, or False to decline
        (per-position ProcessAddPartsDevice then runs). Same linearity
        contract as ProcessAddRunParts; every rank must reach the same
        accept/decline decision from the exchanged metadata."""
        return False

    # -- serving-plane export (round 8; multiverso_tpu/serving/). Runs
    # ON the engine thread inside a Publish barrier dispatch — ordered
    # against every applied Add, at a lockstep window-stream position in
    # multi-process worlds (collectives issued inside are matched, like
    # Request_StoreLoad's fn). CONTRACT: the returned TableSnapshot must
    # be IMMUTABLE and self-contained — it outlives arbitrary later
    # training, so it must not alias buffers a later donated update can
    # invalidate — and its values must equal what a training Get at this
    # stream position would return (apply the updater's access()
    # transform). None = this family opts out of serving.

    def serving_export(self):
        """A serving.snapshot.TableSnapshot of this table's state at the
        current stream position, or None (family not servable)."""
        return None

    # -- memory-accounting ledger (round 13; telemetry/accounting.py).
    # The watchdog plane's byte ledger asks every live table where its
    # state actually LIVES — the measurement substrate the ROADMAP's
    # tiered giant-table work (host-RAM rows + device hot-row cache)
    # will decide hot sets against. CONTRACT: the probe is called from
    # a sampling thread (the watchdog tick / an ops scrape), so it must
    # be pure shape/size arithmetic — never a device sync, a host
    # mirror creation, or a copy. Keys:
    #
    # * ``device_bytes``      — device-resident authoritative state
    #   (the jax store). On jax the number is the LOGICAL array size
    #   (``.nbytes`` — shape math, no sync); on a multi-device process
    #   the per-device share is that divided by the mesh's local device
    #   count — a documented bound, not a measured allocation.
    # * ``host_mirror_bytes`` — replicated host mirrors (a KV table's
    #   numpy values). Exact: these are real host buffers.
    # * ``host_bytes``        — host-authoritative state (host-backed
    #   values, freshness bitmaps, index structures). Exact.

    def ledger_bytes(self) -> Dict[str, int]:
        """Byte placement of this table's live state (see above).
        Default: the generic ``state`` pytree's leaf bytes count as
        device residence; families with mirrors/host planes override.
        ``vars()`` deliberately bypasses properties: a sampling probe
        runs no getter."""
        out = {"device_bytes": 0, "host_mirror_bytes": 0, "host_bytes": 0}
        state = vars(self).get("state")
        if isinstance(state, dict):
            import jax
            out["device_bytes"] = int(sum(
                int(getattr(leaf, "nbytes", 0))
                for leaf in jax.tree.leaves(state)))
        return out

    # Serializable (checkpoint) contract
    def Store(self, stream) -> None:
        raise NotImplementedError

    def Load(self, stream) -> None:
        raise NotImplementedError


class MultiCall:
    """Handle for one BATCHED verb submission (round 19 —
    ``MultiAddAsync``/``MultiGetAsync``/``MV_MultiAdd``/``MV_MultiGet``):
    N (table, verb) records packed into ONE engine mailbox envelope and
    ONE window admission, so the per-verb mailbox round trip — the
    measured ~3k verbs/s GIL wall of the blocking path — amortizes over
    the batch. One counting Waiter covers every tracked member; member
    results land in submission order.

    Failure semantics: ``Wait`` raises the FIRST member error (members
    keep per-message error routing exactly like single verbs — a bad
    table id fails its member only, the rest of the batch applies).
    Unlike single tracked verbs, members do NOT transparently retry a
    ``TransientError`` reply: the retry identity machinery is
    per-message bookkeeping this API exists to avoid, so transients
    surface to the caller (``Wait(return_exceptions=True)`` gives the
    per-member view). Chaos rehearsal worlds that need transparent
    retries should keep issuing single verbs."""

    __slots__ = ("_waiter", "_results", "_n", "_t0")

    def __init__(self, n_tracked: int, n_members: int):
        self._waiter = Waiter(n_tracked) if n_tracked else None
        self._results: list = [None] * n_members
        self._n = n_members
        #: round 22: submission stamp for the worker round-trip digest
        #: (digest.worker.rtt_s) — observed once, at the first Wait
        #: that sees every tracked reply in
        self._t0 = time.perf_counter() if n_tracked else None

    def _member_cb(self, idx: int):
        def _on_reply(msg) -> None:
            self._results[idx] = msg.result
        return _on_reply

    def Wait(self, deadline: Optional[float] = None,
             return_exceptions: bool = False) -> list:
        """Block until every tracked member replied; returns the member
        results in submission order (None for untracked members).
        Bounded by ``deadline`` seconds when given, else
        ``-mv_deadline_s`` (expiry raises ``DeadlineExceeded``)."""
        if self._waiter is not None:
            timeout = (float(deadline) if deadline is not None
                       else fdeadline.timeout_or_none())
            if not self._waiter.Wait(timeout):
                fdeadline.raise_deadline(
                    f"multi-verb batch replies ({self._n} members)")
            if self._t0 is not None:
                tmetrics.digest("digest.worker.rtt_s").observe(
                    time.perf_counter() - self._t0)
                self._t0 = None
        # every member owns its result whichever branch served it: a
        # device-served Get is a read-only view of the device buffer
        # (here, on the caller's thread, not in the engine's reply)
        self._results[:] = [own_result(r) for r in self._results]
        if not return_exceptions:
            for r in self._results:
                if isinstance(r, Exception):
                    raise r
        return list(self._results)


class WorkerTable:
    """Worker half: request construction + waiter bookkeeping."""

    #: short telemetry family tag — concrete tables override (array /
    #: matrix / sparse_matrix / kv) so per-table instrument names read
    #: like "table.matrix0.add.count"
    telemetry_label = "table"

    def __init__(self):
        from multiverso_tpu.zoo import Zoo
        self._zoo = Zoo.Get()
        self.table_id: int = -1
        self._lock = threading.Lock()
        self._waiters: Dict[int, Waiter] = {}
        self._results: Dict[int, Any] = {}
        #: tracked requests' (msg_type, payload, src) — kept until Wait
        #: so a TransientError reply can resubmit the SAME request under
        #: the SAME msg_id (the server dedup window's retry identity)
        self._inflight: Dict[int, tuple] = {}
        self._tele: Optional[Dict[str, Any]] = None
        # -- write combining (round 7; -mv_write_combine) -----------------
        #: buffered fire-and-forget Add payloads awaiting one combined
        #: mailbox hop, plus their shared option and the worker whose
        #: run this is (an option/worker change flushes first)
        self._wc_buf: list = []
        self._wc_option: Optional[AddOption] = None
        self._wc_src: int = 0
        self._wc_ctx = None      # first buffered member's trace context
        # -- staleness-bounded Get cache (round 7; -mv_get_staleness) -----
        #: request key -> (engine window_epoch at fill, table write
        #: epoch at fill, pristine result); insertion-ordered for a
        #: cheap oldest-entry eviction
        self._gc_cache: Dict[Any, tuple] = {}
        #: results parked for cache-served pseudo handles (negative ids)
        self._gc_results: Dict[int, Any] = {}
        self._gc_next_hit = -1
        #: msg_id -> request key for in-flight Gets whose reply should
        #: (re)fill the cache
        self._gc_fill: Dict[int, Any] = {}
        #: bumped by every Add THIS worker process issues to this table
        #: (tracked, fire-and-forget, or buffered): read-your-writes —
        #: a cached read never survives the owner's own write
        self._write_epoch = 0
        self._gc_enabled: Optional[bool] = None   # fixed per world

    def _tele_verbs(self) -> Dict[str, Any]:
        """Per-table per-verb count/byte instruments, fetched lazily —
        table_id is only assigned after construction (CreateTable)."""
        if self._tele is None:
            base = f"table.{self.telemetry_label}{self.table_id}"
            self._tele = {
                "get_n": tmetrics.counter(f"{base}.get.count"),
                "get_b": tmetrics.counter(f"{base}.get.bytes"),
                "add_n": tmetrics.counter(f"{base}.add.count"),
                "add_b": tmetrics.counter(f"{base}.add.bytes"),
            }
        return self._tele

    # -- request plumbing ---------------------------------------------------

    def _submit(self, msg_type: MsgType, payload: Dict[str, Any],
                worker_id: Optional[int] = None, track: bool = True) -> int:
        """Build + enqueue a request message; returns msg_id
        (reference table.cpp:41-82 GetAsync/AddAsync).

        ``track=False`` is fire-and-forget: no Waiter or result slot is
        allocated, so high-rate async pushes (one per minibatch for a whole
        training run) don't leak bookkeeping; server-side failures are still
        logged by the engine. Per-table FIFO ordering at the server mailbox
        guarantees a later tracked Get observes the push."""
        if track:
            # a tracked verb is a global ordering point: every table's
            # combined-write buffer flushes first so the reply implies
            # at least as much progress as the serial message stream
            # would have shown (cheap no-op when nothing is buffered)
            self._zoo.flush_combined_adds()
        msg_id = next_msg_id()
        src = self._zoo.current_worker_id() if worker_id is None else worker_id
        if track:
            waiter = Waiter(1)
            with self._lock:
                self._waiters[msg_id] = waiter
                self._inflight[msg_id] = (msg_type, payload, src)
            msg = Message(msg_type=msg_type, table_id=self.table_id,
                          msg_id=msg_id, src=src, payload=payload,
                          waiter=waiter, on_reply=self._on_reply)
        else:
            msg = Message(msg_type=msg_type, table_id=self.table_id,
                          msg_id=msg_id, src=src, payload=payload)
        # telemetry: carry the worker span's context across the mailbox
        # hop (the engine parents its dispatch span here) and open the
        # flow arrow Perfetto draws between the two threads
        msg.trace_ctx = ttrace.current_ctx()
        ttrace.flow_start(msg.trace_ctx)
        self._zoo.SendToServer(msg)
        return msg_id

    def _on_reply(self, msg: Message) -> None:
        with self._lock:
            # a reply landing after the request was abandoned (deadline
            # expiry cleaned its slots) must not repopulate _results —
            # nothing would ever pop it again
            if msg.msg_id in self._waiters:
                self._results[msg.msg_id] = msg.result

    def _resubmit(self, msg_id: int) -> Waiter:
        """Re-send a tracked request under its ORIGINAL msg_id after a
        TransientError: the server's (src, msg_id) dedup window is what
        makes the retry at-most-once for Adds."""
        with self._lock:
            msg_type, payload, src = self._inflight[msg_id]
            waiter = Waiter(1)
            self._waiters[msg_id] = waiter
            self._results.pop(msg_id, None)
        msg = Message(msg_type=msg_type, table_id=self.table_id,
                      msg_id=msg_id, src=src, payload=payload,
                      waiter=waiter, on_reply=self._on_reply)
        msg.trace_ctx = ttrace.current_ctx()
        ttrace.flow_start(msg.trace_ctx)
        self._zoo.SendToServer(msg)
        return waiter

    def Wait(self, msg_id: int) -> Any:
        """Block until the request's reply arrived; returns its result
        (reference table.cpp:84-95).

        Failsafe layer on top of the reference semantics: with
        ``-mv_deadline_s`` set the wait is bounded (expiry raises
        ``DeadlineExceeded`` with the diagnostic bundle; unset blocks
        exactly as before), and a ``TransientError`` reply is retried
        up to ``-mv_max_retries`` times with exponential backoff +
        jitter — safe because retries reuse the msg_id and the server
        dedup window never double-applies an Add."""
        if msg_id < 0:
            # staleness-bounded cache hit (GetAsync): the parked copy IS
            # the result — no waiter, no mailbox round trip
            with self._lock:
                return self._gc_results.pop(msg_id)
        with self._lock:
            waiter = self._waiters.get(msg_id)
        CHECK(waiter is not None, f"unknown msg_id {msg_id}")
        max_retries = _max_retries_flag()
        attempt = 0
        # the wait for the reply, which worker.get / worker.add (closed
        # at the enqueue) leave out
        with ttrace.span("worker.wait", cat="worker",
                         args=({"table_id": self.table_id}
                               if ttrace.enabled() else None)):
            while True:
                if not waiter.Wait(fdeadline.timeout_or_none()):
                    try:
                        # bundle first (it reports THIS in-flight request),
                        # then abandon it: every bookkeeping slot is dropped
                        # (an app catching DeadlineExceeded per request must
                        # not leak a waiter + pinned payload per miss;
                        # _on_reply ignores replies to abandoned ids)
                        fdeadline.raise_deadline(
                            f"table {self.table_id} reply to msg_id {msg_id}")
                    finally:
                        with self._lock:
                            self._waiters.pop(msg_id, None)
                            self._inflight.pop(msg_id, None)
                            self._results.pop(msg_id, None)
                            self._gc_fill.pop(msg_id, None)
                with self._lock:
                    result = self._results.pop(msg_id, None)
                if (isinstance(result, TransientError)
                        and attempt < max_retries):
                    attempt += 1
                    tmetrics.counter("failsafe.retries").inc()
                    backoff = _RETRY_BACKOFF_BASE_S * (2 ** (attempt - 1))
                    backoff += random.random() * _RETRY_BACKOFF_BASE_S
                    Log.Debug("table %d msg_id %d transient (%r) — retry "
                              "%d/%d in %.3fs", self.table_id, msg_id,
                              result, attempt, max_retries, backoff)
                    time.sleep(backoff)
                    waiter = self._resubmit(msg_id)
                    continue
                break
        with self._lock:
            self._waiters.pop(msg_id, None)
            self._inflight.pop(msg_id, None)
            fill = self._gc_fill.pop(msg_id, None)
        if isinstance(result, Exception):
            raise result
        if fill is not None:
            self._gc_store(fill[0], result, fill[1], fill[2])
        return result

    # -- public verbs (concrete tables wrap these with typed signatures) ----

    def GetAsync(self, payload: Dict[str, Any],
                 option: Optional[GetOption] = None) -> int:
        with monitor_region("WORKER_TABLE_SYNC_GET"):  # reference table.cpp:28-38
            opt = option or GetOption(worker_id=self._zoo.current_worker_id())
            payload = dict(payload)
            payload["option"] = opt
            tele = self._tele_verbs()
            tele["get_n"].inc()
            tele["get_b"].inc(payload_nbytes(payload))
            hit, key = self._gc_probe(payload)
            if hit is not None:
                return hit
            with ttrace.span("worker.get", cat="worker",
                             args=({"table_id": self.table_id}
                                   if ttrace.enabled() else None)):
                handle = self._submit(MsgType.Request_Get, payload,
                                      worker_id=opt.worker_id)
            if key is not None:
                # miss under an active staleness bound: the reply
                # (re)fills this request's cache entry (Wait). The fill
                # epoch is captured NOW — the engine serves the Get at
                # some window >= this one, so dating the entry from the
                # submit keeps "at most N windows since the fill"
                # honest however late the caller Waits (dating it at
                # Wait time would let a long async gap launder
                # arbitrarily stale data as fresh).
                eng = self._zoo.server_engine
                with self._lock:
                    # BOTH clocks captured at SUBMIT: the window epoch
                    # (see above) AND this process's write epoch — a
                    # concurrent worker thread's Add landing between
                    # submit and Wait must invalidate the entry, but a
                    # Wait-time read would stamp the entry with the
                    # post-Add epoch and launder the stale value as
                    # fresh (unmasked by the round-12 per-shard
                    # staleness clock; the old global clock usually
                    # aged such entries out by accident)
                    self._gc_fill[handle] = (
                        key, eng.epoch_for_table(self.table_id),
                        self._write_epoch)
            return handle

    def AddAsync(self, payload: Dict[str, Any],
                 option: Optional[AddOption] = None,
                 track: bool = True) -> int:
        with monitor_region("WORKER_TABLE_SYNC_ADD"):
            opt = option or AddOption(worker_id=self._zoo.current_worker_id())
            payload = dict(payload)
            payload["option"] = opt
            tele = self._tele_verbs()
            tele["add_n"].inc()
            tele["add_b"].inc(payload_nbytes(payload))
            # read-your-writes: any Add this process issues (tracked,
            # fire-and-forget, or buffered below) invalidates the
            # table's cached Gets
            self._write_epoch += 1
            with ttrace.span("worker.add", cat="worker",
                             args=({"table_id": self.table_id}
                                   if ttrace.enabled() else None)):
                if not track:
                    if self._wc_try_buffer(payload, opt):
                        return 0
                    # non-combinable fire-and-forget push: earlier
                    # buffered Adds must still precede it (per-table
                    # FIFO)
                    self.FlushCombined()
                return self._submit(MsgType.Request_Add, payload,
                                    worker_id=opt.worker_id, track=track)

    # -- batched verbs (round 19; MultiCall) --------------------------------

    def _multi_member(self, kind: str, payload: Dict[str, Any],
                      option, call: MultiCall, idx: int,
                      track: bool) -> Message:
        """Build ONE member message of a batched submission: the same
        bookkeeping a single verb pays (option defaulting, per-table
        telemetry, read-your-writes epoch bump) minus the mailbox hop —
        the whole batch ships through one envelope
        (``Zoo.SendToServerMulti``)."""
        CHECK(kind in ("A", "G"), f"multi member kind {kind!r}")
        if kind == "A":
            opt = option or AddOption(
                worker_id=self._zoo.current_worker_id())
            msg_type = MsgType.Request_Add
        else:
            opt = option or GetOption(
                worker_id=self._zoo.current_worker_id())
            msg_type = MsgType.Request_Get
            track = True        # a Get's whole point is its result
        payload = dict(payload)
        payload["option"] = opt
        tele = self._tele_verbs()
        if kind == "A":
            tele["add_n"].inc()
            tele["add_b"].inc(payload_nbytes(payload))
            # read-your-writes: the batched Add invalidates this
            # table's cached Gets exactly like a single Add would
            self._write_epoch += 1
        else:
            tele["get_n"].inc()
            tele["get_b"].inc(payload_nbytes(payload))
        msg = Message(
            msg_type=msg_type, table_id=self.table_id,
            msg_id=next_msg_id(), src=opt.worker_id, payload=payload,
            waiter=call._waiter if track else None,
            on_reply=call._member_cb(idx) if track else None)
        msg.trace_ctx = ttrace.current_ctx()
        return msg

    def MultiAddAsync(self, payloads, option=None,
                      track: bool = True) -> MultiCall:
        """Submit N Adds to THIS table as one batch (one mailbox hop,
        one window admission); per-table op order is submission order
        — the batch flattens into the existing verb stream, so the
        result is bit-identical to N serial ``AddAsync`` calls.
        ``payloads`` is a list of the same payload dicts ``AddAsync``
        takes. ``track=False`` is the fire-and-forget form."""
        return submit_multi([(self, "A", p) for p in payloads],
                            option=option, track=track)

    def MultiGetAsync(self, payloads, option=None) -> MultiCall:
        """Submit N Gets to THIS table as one batch; ``Wait`` returns
        the results in submission order. Bypasses the staleness-bounded
        Get cache (the cache exists to skip round trips; the batch IS
        one round trip)."""
        return submit_multi([(self, "G", p) for p in payloads],
                            option=option)

    def MultiAdd(self, payloads, option=None) -> None:
        """Blocking batched Add: ``MultiAddAsync`` + ``Wait``."""
        # unbounded-ok: MultiCall.Wait honors -mv_deadline_s internally
        self.MultiAddAsync(payloads, option=option).Wait()

    def MultiGet(self, payloads, option=None) -> list:
        """Blocking batched Get: results in submission order."""
        # unbounded-ok: MultiCall.Wait honors -mv_deadline_s internally
        return self.MultiGetAsync(payloads, option=option).Wait()

    # -- write combining (round 7; -mv_write_combine) -----------------------

    def _combinable_fire_forget(self, payload: Dict[str, Any]) -> bool:
        """True when ``payload`` (an Add's, option included) may join
        this table's combined-write buffer. Default False — a table
        opts in by overriding this plus _combine_fire_forget with a
        merge whose ONE combined apply is observationally identical to
        applying the members in order (concatenated row/key batches
        are; whole-table float sums are only for linear updaters, which
        the worker half can't see, so those stay out)."""
        return False

    def _combine_fire_forget(self, payloads: list) -> Dict[str, Any]:
        """Merge buffered payloads (each accepted by
        _combinable_fire_forget, sharing one option) into ONE payload.
        Member order must be preserved wherever order is observable
        (key first-sight order, duplicate-row pre-combine order)."""
        raise NotImplementedError

    def _wc_try_buffer(self, payload: Dict[str, Any],
                       opt: AddOption) -> bool:
        """Buffer one fire-and-forget Add for combining; False when the
        payload (or config) wants the normal per-message path. The cap
        counts MEMBERS, not bytes — call sequences are program-
        structural and therefore lockstep across SPMD ranks, while
        payload bytes can skew per rank and would diverge the
        multi-process verb streams (sync/server.py flag help)."""
        cap = _write_combine_flag()
        if cap <= 0 or not self._combinable_fire_forget(payload):
            return False
        eng = self._zoo.server_engine
        if eng is None or not getattr(eng, "WRITE_COMBINE_OK", False):
            return False    # BSP counts Add MESSAGES into its clocks
        with self._lock:
            if self._wc_buf and self._wc_option != opt:
                self._flush_wc_locked()
            if self._wc_buf:
                tmetrics.counter("worker.write_combine_hits").inc()
            else:
                # the combined message belongs to the ADDs' trace, not
                # whichever later verb happens to trigger the flush:
                # carry the first member's span context
                self._wc_ctx = ttrace.current_ctx()
            self._wc_buf.append(payload)
            self._wc_option = opt
            self._wc_src = opt.worker_id
            if len(self._wc_buf) >= cap:
                self._flush_wc_locked()
        return True

    def FlushCombined(self) -> None:
        """Ship this table's combined-write buffer (no-op when empty).
        Flush points: a tracked verb on ANY table (_submit), a
        non-combinable push to THIS table, the member-count cap, and
        the Zoo's barrier/drain/shutdown paths."""
        with self._lock:
            self._flush_wc_locked()

    def _flush_wc_locked(self) -> None:
        if not self._wc_buf:
            return
        bufs, opt, src = self._wc_buf, self._wc_option, self._wc_src
        ctx = getattr(self, "_wc_ctx", None)
        self._wc_buf, self._wc_option, self._wc_ctx = [], None, None
        payload = bufs[0] if len(bufs) == 1 else \
            self._combine_fire_forget(bufs)
        payload["option"] = opt
        msg = Message(msg_type=MsgType.Request_Add, table_id=self.table_id,
                      msg_id=next_msg_id(), src=src, payload=payload)
        msg.trace_ctx = ctx
        ttrace.flow_start(msg.trace_ctx)
        self._zoo.SendToServer(msg)

    # -- staleness-bounded Get cache (round 7; -mv_get_staleness) -----------

    def _gc_ok(self) -> bool:
        """Cache eligibility, fixed per world: flag aside, the engine
        must be the async Server (BSP round accounting counts Get
        messages) and the world SINGLE-process — a cache hit removes a
        verb from the stream, which the multi-process SPMD collective
        contract cannot tolerate (rank A hitting while rank B misses
        would diverge the lockstep verb sequences)."""
        ok = self._gc_enabled
        if ok is None:
            from multiverso_tpu.parallel import multihost
            eng = self._zoo.server_engine
            ok = (eng is not None
                  and getattr(eng, "GET_CACHE_OK", False)
                  and multihost.world_size() <= 1)
            self._gc_enabled = ok
        return ok

    def _gc_key(self, payload: Dict[str, Any]):
        """Hashable request identity (option included), or None when a
        part can't be keyed — those Gets never cache."""
        parts = [self.table_id]
        for k in sorted(payload):
            v = payload[k]
            if isinstance(v, np.ndarray):
                parts.append((k, v.dtype.str, v.shape, v.tobytes()))
            elif v is None or isinstance(v, (bool, int, float, str, bytes)):
                parts.append((k, v))
            elif isinstance(v, (GetOption, AddOption)):
                parts.append((k, repr(v)))
            else:
                return None
        return tuple(parts)

    def _gc_probe(self, payload: Dict[str, Any]):
        """Serve a repeated Get from the cache when within the
        staleness bound. Returns ``(pseudo_handle, None)`` on a hit
        (negative id — Wait pops the parked copy), ``(None, key)`` on a
        cacheable miss (the caller registers the key so the reply
        refills the entry), or ``(None, None)`` when caching is off /
        the request can't be keyed."""
        staleness = _get_staleness_flag()
        if staleness <= 0 or not self._gc_ok():
            return None, None
        key = self._gc_key(payload)
        if key is None:
            return None, None
        eng = self._zoo.server_engine
        with self._lock:
            ent = self._gc_cache.get(key)
            if ent is not None:
                fill_epoch, fill_wep, result = ent
                # per-shard epoch (round 12): the staleness clock is
                # the stream applying THIS table's verbs — a busy
                # neighbour shard must not age this entry
                if (fill_wep == self._write_epoch
                        and (eng.epoch_for_table(self.table_id)
                             - fill_epoch) <= staleness):
                    tmetrics.counter("worker.get_cache_hits").inc()
                    self._gc_next_hit -= 1
                    hid = self._gc_next_hit
                    self._gc_results[hid] = copy_result(result)
                    return hid, None
                del self._gc_cache[key]   # expired: drop, refill below
        return None, key

    def worker_ledger_bytes(self) -> Dict[str, int]:
        """Worker-half buffered bytes for the accounting ledger (round
        13): the combined-write buffer awaiting its one mailbox hop and
        the staleness-bounded Get cache's parked result copies. Exact
        host bytes, one short lock — called from the watchdog sampling
        thread, never from a verb path."""
        with self._lock:
            wc = sum(payload_nbytes(p) for p in self._wc_buf)
            gc = sum(_result_nbytes(ent[2])
                     for ent in self._gc_cache.values())
            gc += sum(_result_nbytes(r)
                      for r in self._gc_results.values())
        return {"write_combine_bytes": int(wc),
                "get_cache_bytes": int(gc)}

    def _gc_store(self, key, result, fill_epoch: int,
                  fill_wep: int) -> None:
        """File one fetched result under its request key, dated at the
        SUBMIT-time window AND write epochs (GetAsync captured both —
        see there)."""
        with self._lock:
            if len(self._gc_cache) >= _GET_CACHE_ENTRIES:
                self._gc_cache.pop(next(iter(self._gc_cache)))
            self._gc_cache[key] = (fill_epoch, fill_wep,
                                   copy_result(result))


def submit_multi(records, option=None, track: bool = True) -> MultiCall:
    """Cross-table batched submission (round 19): ``records`` is a list
    of ``(worker_table, kind, payload)`` with ``kind`` ``'A'``/``'G'``
    and ``payload`` the dict the table's ``AddAsync``/``GetAsync``
    takes. All records ship in ONE engine mailbox envelope and enter
    the verb stream in list order (a sharded engine splits the batch
    per shard, preserving each table's order — routing is by table, so
    per-table order survives the split). Gets are always tracked;
    ``track=False`` makes the Adds fire-and-forget. Returns the batch's
    :class:`MultiCall`.

    SPMD contract: like every verb, batches are program-structural —
    every rank must submit the same record sequence at the same
    position (the members ARE ordinary stream verbs after the engine
    flattens the envelope)."""
    from multiverso_tpu.zoo import Zoo
    n_tracked = sum(1 for _, kind, _ in records
                    if kind == "G" or track)
    if n_tracked == 0:
        # untracked batch: per-table FIFO still holds — earlier
        # BUFFERED fire-and-forget Adds to a member's table must ship
        # ahead of the member (the single-verb path's FlushCombined-on-
        # non-combinable-push rule; a TRACKED batch flushes globally in
        # SendToServerMulti instead)
        for table in {id(t): t for t, _, _ in records}.values():
            table.FlushCombined()
    call = MultiCall(n_tracked, len(records))
    members = [table._multi_member(kind, payload, option, call, idx,
                                   track)
               for idx, (table, kind, payload) in enumerate(records)]
    if members:
        Zoo.Get().SendToServerMulti(members, tracked=n_tracked > 0)
    return call


def CreateTable(option: TableOption):
    """Instantiate server + worker halves and wire them to the engine
    (reference table_factory.h:16-27 + MV_CreateTable barrier semantics are
    in api.MV_CreateTable)."""
    from multiverso_tpu.zoo import Zoo
    CHECK(option.compress is None or option._supports_compress,
          f"table type {type(option).__name__} has no compressed wire "
          f"(compress={option.compress!r})")
    zoo = Zoo.Get()
    server_table = option.make_server(zoo)
    # the creation record rides the server half: an elastic epoch
    # transition re-runs make_server against the new mesh and restores
    # state from the cut frame (elastic/rebalance.rebuild_world)
    server_table._mv_option = option
    table_id = zoo.RegisterServerTable(server_table)
    worker_table = option.make_worker(zoo)
    worker_table.table_id = table_id
    zoo.RegisterWorkerTable(worker_table)
    return worker_table
