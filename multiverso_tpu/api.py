"""Public ``MV_*`` API.

Behavioral equivalent of reference include/multiverso/multiverso.h:9-64 /
src/multiverso.cpp: init/shutdown/barrier, rank & size, worker/server id
maps, table creation (+ implicit barrier), programmatic flags, and
``MV_Aggregate`` allreduce. ``MV_NetBind``/``MV_NetConnect`` (explicit
endpoints, multiverso.h:54-63 — the reference's MPI-free ZMQ deployment
path) map to launcher-free ``jax.distributed`` bring-up: the declarations
feed the next MV_Init, rank 0's endpoint being the coordinator.
"""

from __future__ import annotations

import threading
from typing import List, Optional

import numpy as np

from multiverso_tpu.utils.configure import SetCMDFlag
from multiverso_tpu.utils.log import CHECK, Log
from multiverso_tpu.zoo import Zoo


def MV_Init(argv: Optional[List[str]] = None, devices=None) -> List[str]:
    """Bring up the runtime (reference multiverso.h:9, zoo.cpp:41-103).

    Returns leftover argv entries (flags are stripped in place like
    ParseCMDFlags)."""
    return Zoo.Get().Start(argv, devices=devices)


def MV_ShutDown(finalize_net: bool = True) -> None:
    """reference multiverso.h:13; finalize_net=False mirrors the unit tests'
    MV_ShutDown(false) (multiverso_env.h:17) which skips MPI_Finalize —
    here it keeps the process-level jax state warm either way."""
    Zoo.Get().Stop(finalize_net)
    Zoo._reset_for_tests()
    from multiverso_tpu.utils.configure import ResetFlagsToDefaults
    ResetFlagsToDefaults()
    # forget MV_NetBind/MV_NetConnect declarations: a retry after a failed
    # explicit bring-up must be able to run single-process (jax.distributed
    # itself, once up, stays up — process-level state)
    from multiverso_tpu.parallel import multihost
    multihost.net_reset()


def MV_Barrier() -> None:
    Zoo.Get().Barrier()


def MV_Rank() -> int:
    return Zoo.Get().rank


def MV_Size() -> int:
    return Zoo.Get().size


def MV_NumWorkers() -> int:
    return Zoo.Get().num_workers


def MV_NumServers() -> int:
    return Zoo.Get().num_servers


def MV_WorkerId() -> int:
    return Zoo.Get().current_worker_id()


def MV_ServerId() -> int:
    return 0 if Zoo.Get().node.is_server() else -1


def MV_WorkerIdToRank(worker_id: int) -> int:
    return Zoo.Get().worker_id_to_rank(worker_id)


def MV_ServerIdToRank(server_id: int) -> int:
    return Zoo.Get().server_id_to_rank(server_id)


def MV_CreateTable(option):
    """Create a table and barrier (reference multiverso.h:34-41)."""
    from multiverso_tpu.tables.base import CreateTable
    table = CreateTable(option)
    # reference MV_CreateTable barriers across ranks; in-process worker
    # threads create tables before spawning, so a trivial barrier suffices
    # when only the creating thread exists.
    return table


def MV_SetFlag(name: str, value) -> None:
    SetCMDFlag(name, value)


def MV_MultiAddAsync(ops, option=None, track: bool = True):
    """Batched cross-table Add (round 19): ``ops`` is a list of
    ``(table, payload)`` pairs — ``table`` a worker-table handle,
    ``payload`` the dict its ``AddAsync`` takes (e.g. ``{"row_ids":
    ids, "values": deltas}`` for matrix, ``{"keys": k, "values": v}``
    for kv). The whole batch rides ONE engine mailbox message and one
    window admission, amortizing the per-verb round trip the blocking
    path pays (a mailbox hop a verb, behind the interpreter lock);
    per-table op order is submission order, so the result is
    bit-identical to issuing the Adds serially. Returns a ``MultiCall``
    — ``Wait()`` blocks for the replies. ``track=False`` is fire-and-forget (returns immediately
    with nothing to wait on). The reference's worker talks to tables
    through coalescable Get/Add with an async buffer hand-off (PAPER.md
    ASyncBuffer); this is that idiom as a first-class verb."""
    from multiverso_tpu.tables.base import submit_multi
    return submit_multi([(t, "A", p) for t, p in ops],
                        option=option, track=track)


def MV_MultiAdd(ops, option=None, track: bool = True) -> None:
    """Blocking form of :func:`MV_MultiAddAsync` (no-op wait when
    ``track=False``)."""
    # unbounded-ok: MultiCall.Wait honors -mv_deadline_s internally
    # (raise_deadline on expiry), like WorkerTable.Wait
    MV_MultiAddAsync(ops, option=option, track=track).Wait()


def MV_MultiGetAsync(ops, option=None):
    """Batched cross-table Get: ``ops`` is a list of ``(table,
    payload)`` pairs; returns a ``MultiCall`` whose ``Wait()`` yields
    the results in submission order. One mailbox hop and one window
    admission for the whole batch — and the window engine still
    coalesces/dedups the members exactly as if they had queued
    individually."""
    from multiverso_tpu.tables.base import submit_multi
    return submit_multi([(t, "G", p) for t, p in ops], option=option)


def MV_MultiGet(ops, option=None) -> list:
    """Blocking form of :func:`MV_MultiGetAsync`: the member results in
    submission order."""
    # unbounded-ok: MultiCall.Wait honors -mv_deadline_s internally
    return MV_MultiGetAsync(ops, option=option).Wait()


def MV_Aggregate(data: np.ndarray) -> np.ndarray:
    """Elementwise-sum allreduce across workers
    (reference multiverso.h:45, src/multiverso.cpp:53-56)."""
    return Zoo.Get().Aggregate(data)


def MV_NetBind(rank: int, endpoint: str) -> int:
    """Declare this process's rank + endpoint for launcher-free bring-up
    (reference MV_NetBind, multiverso.h:55 / zmq_net.h:64-81: the
    MPI-free ZMQ deployment path). TPU mapping: the declarations feed
    ``jax.distributed`` at the next MV_Init — rank 0's endpoint is the
    coordinator the world rendezvouses on. Call before MV_Init; 0 on
    success, -1 on error (reference return convention)."""
    from multiverso_tpu.parallel import multihost
    return multihost.net_bind(rank, endpoint)


def MV_NetConnect(ranks, endpoints) -> int:
    """Declare the full world as parallel (ranks, endpoints) lists
    (reference MV_NetConnect, multiverso.h:56 / zmq_net.h:83-110).
    Requires a prior MV_NetBind; the next MV_Init wires jax.distributed
    from this world. 0 on success, -1 on error."""
    from multiverso_tpu.parallel import multihost
    return multihost.net_connect(ranks, endpoints)


def MV_NetFinalize() -> None:
    """Tear down the explicit net layer (reference MV_NetFinalize,
    multiverso.h:65 / src/multiverso.cpp:66-68 finalizes the transport):
    forgets MV_NetBind/MV_NetConnect declarations and shuts down
    ``jax.distributed`` if this runtime brought it up. Call after
    MV_ShutDown when the process is done with distributed work."""
    from multiverso_tpu.parallel import multihost
    multihost.net_finalize()


def MV_SaveCheckpoint(uri: str) -> int:
    """Store every registered server table (+ updater aux state) to ``uri``
    (framework-level driver over the per-table Serializable contract,
    reference table_interface.h:61-70 — see checkpoint.py)."""
    from multiverso_tpu.checkpoint import save_checkpoint
    return save_checkpoint(uri)


def MV_LoadCheckpoint(uri: str) -> int:
    """Restore every registered server table from ``uri``."""
    from multiverso_tpu.checkpoint import load_checkpoint
    return load_checkpoint(uri)


def MV_PublishSnapshot() -> int:
    """Publish an immutable, versioned, cross-table-consistent snapshot
    of every live table for the serving plane (multiverso_tpu/serving/);
    returns the new version number. The cut rides the engine window
    stream as a barrier, so all Adds admitted before the call are in and
    none after — COLLECTIVE in a multi-process world (every process
    calls it at the same verb-stream position, like MV_Barrier; the
    version numbers then agree on every rank). Retention:
    ``-mv_serving_keep`` newest versions stay live; pin older ones with
    :func:`MV_PinVersion`. Not available in ``-ma`` mode (CHECK-fails):
    model-average worlds run no engine AND can create no tables, so
    there is nothing to cut."""
    from multiverso_tpu.serving import publish
    return publish()


def MV_ServingLookup(table, ids=None, version: Optional[int] = None,
                     deadline: Optional[float] = None) -> np.ndarray:
    """Serve ``ids`` of ``table`` (a worker-table handle or table id)
    from the published snapshot ``version`` (None = latest) WITHOUT
    touching the engine verb stream. ``ids=None`` reads the whole
    table; KV tables take int64 keys (absent keys read as 0). Thread-
    safe and micro-batched: concurrent callers of one table coalesce
    into one fused gather. ``deadline`` (seconds, default
    ``-mv_deadline_s``) bounds the wait with ``DeadlineExceeded``;
    admission past ``-mv_serving_max_inflight`` raises a typed
    ``ServingOverloaded`` instead of queueing unboundedly."""
    from multiverso_tpu.serving import get_plane
    table_id = getattr(table, "table_id", table)
    CHECK(isinstance(table_id, int) and table_id >= 0,
          f"MV_ServingLookup: bad table {table!r}")
    return get_plane().frontend.lookup(table_id, ids, version=version,
                                       deadline=deadline)


def MV_PinVersion(version: int) -> int:
    """Hold snapshot ``version`` live past the ``-mv_serving_keep``
    retention window (pins nest); returns the version. Release with
    :func:`MV_UnpinVersion`."""
    from multiverso_tpu.serving import get_plane
    return get_plane().store.pin(version)


def MV_UnpinVersion(version: int) -> None:
    """Release one :func:`MV_PinVersion` pin; a fully-unpinned version
    outside the retention window is evicted immediately."""
    from multiverso_tpu.serving import get_plane
    get_plane().store.unpin(version)


def MV_WorkerContext(worker_id: int):
    """Bind the calling thread to a worker id for the ``with`` block —
    in-process worker threads stand in for the reference's MPI rank
    workers (``-num_workers=N``); table verbs issued inside carry this
    worker id (per-worker AdaGrad state, BSP clocks, dirty-row bits)."""
    from multiverso_tpu.zoo import Zoo
    return Zoo.Get().worker_context(worker_id)


_profiler_lock = threading.Lock()
_profiler_active = False


def MV_StartProfiler(logdir: str) -> None:
    """Start a JAX profiler trace (xplane) into ``logdir`` — the
    device-side complement of the host-side Monitor dashboard (SURVEY.md
    §5: 'jax profiler/xplane traces + the same named-region dashboard');
    view with TensorBoard or xprof. One trace at a time — a second start
    CHECK-fails with a clear message instead of raising from deep inside
    jax. While the trace runs, telemetry spans (telemetry/trace.py)
    bridge into ``jax.profiler.TraceAnnotation`` so host spans appear on
    the xplane timeline alongside the device ops they dispatched."""
    global _profiler_active
    import jax
    with _profiler_lock:
        CHECK(not _profiler_active,
              "MV_StartProfiler: a profiler trace is already active — "
              "one trace at a time (call MV_StopProfiler first)")
        jax.profiler.start_trace(logdir)
        _profiler_active = True
    from multiverso_tpu.telemetry import trace as ttrace
    ttrace.set_xplane(True)


def MV_StopProfiler() -> None:
    """Stop the trace started by ``MV_StartProfiler`` and flush it.
    Without an active trace this is a logged no-op."""
    global _profiler_active
    from multiverso_tpu.telemetry import trace as ttrace
    with _profiler_lock:
        if not _profiler_active:
            Log.Error("MV_StopProfiler without an active MV_StartProfiler "
                      "trace — no-op")
            return
        ttrace.set_xplane(False)
        import jax
        jax.profiler.stop_trace()
        _profiler_active = False


def MV_MetricsSnapshot() -> dict:
    """Job-wide telemetry snapshot: every registered instrument
    (telemetry/metrics.py) summed across hosts — ``{name: {"type":
    ..., "value"/"count"/"p50"/...}}``. COLLECTIVE in a multi-process
    world: every process must call it at the same point with the engine
    quiesced (after tracked verbs have replied / after MV_Barrier),
    exactly like Dashboard.AggregateAcrossHosts. Identity
    single-process."""
    from multiverso_tpu.telemetry import metrics
    return metrics.merged_snapshot()


def MV_DumpTrace(path: str) -> str:
    """Write the buffered telemetry spans (``-trace=true``) as Chrome
    trace-event JSON to ``path`` — load it in Perfetto
    (https://ui.perfetto.dev) or chrome://tracing. Per-rank in multihost
    jobs (each rank dumps its own spans). Returns ``path``."""
    from multiverso_tpu.telemetry import trace
    return trace.dump(path)


def MV_DumpFlightRecorder(path: str) -> str:
    """Write the always-on flight recorder's event ring
    (``-mv_flight_events``; telemetry/flight.py) as JSONL to ``path``:
    a header line (rank, pid, recorded/dropped counts), then one event
    per line — window admitted/exchanged/applied with exchange SEQ,
    fence causes, barriers, CRC retries, dedup hits, snapshot
    publish/evict, serving dispatch/shed, actor poison. Per-rank and
    never collective; align several ranks' dumps with ``python -m
    multiverso_tpu.telemetry.forensics``. Returns ``path``."""
    from multiverso_tpu.telemetry import flight
    return flight.dump(path)


def MV_ElasticSync() -> int:
    """Elastic sync point (requires ``-mv_elastic``): a LOCKSTEP
    rendezvous every active member calls at the same loop position.
    Applies at most one staged membership transition (drain / admit)
    at a fenced window-stream cut and always refreshes the retained
    snapshot cut (the silent-death rollback anchor). Returns the
    membership epoch in effect."""
    from multiverso_tpu import elastic
    return elastic.sync()


def MV_ElasticLeave() -> int:
    """Gracefully drain THIS member from the running world: stages the
    departure and runs the final collective sync that applies it (the
    other members reach the same position via ``MV_ElasticSync``).
    The process stays alive — ``MV_ElasticJoin`` re-admits it later.
    Returns the epoch departed at."""
    from multiverso_tpu import elastic
    return elastic.leave()


def MV_ElasticJoin() -> int:
    """(Re)admission of a departed member: stages the join, parks until
    the live members reach a sync point, downloads every table from
    the shard-move plane (the snapshot cut the world fenced at),
    rebuilds them on the new world's mesh and commits. Returns the
    epoch joined at."""
    from multiverso_tpu import elastic
    # unbounded-ok: every RPC inside elastic.join() is bounded by the
    # elastic control timeout (the joiner legitimately parks until the
    # live members reach their next sync point)
    return elastic.join()


def MV_ElasticEpoch() -> int:
    """The membership epoch in effect (0 = boot world / plane off)."""
    from multiverso_tpu import elastic
    return elastic.epoch()


def MV_ElasticMembers() -> tuple:
    """Boot ranks of the current world's members (empty tuple when the
    elastic plane is off)."""
    from multiverso_tpu import elastic
    return elastic.members()


def MV_PolicySync(timeout: float = 60.0) -> list:
    """Policy actuation point (requires ``-mv_policy``): a LOCKSTEP
    call every active member makes at the same loop position (the
    MV_SaveCheckpoint / MV_ElasticSync discipline). Pulls the ONE
    agreed staged-action list from the policy control authority's
    rendezvous, installs route/tune actions at this rank's fenced
    engine cut, and runs at most one guarded elastic drain (the sick
    rank's MV_ElasticLeave against the survivors' MV_ElasticSync).
    Returns the actions actuated ([] while the plane is off —
    single-process worlds actuate from the policy thread and rarely
    have anything left to flush here)."""
    from multiverso_tpu import policy
    return policy.sync_point(timeout=timeout)


def MV_PolicyReport() -> dict:
    """The policy plane's local action report (the ``/actions`` body):
    guard settings, install/revert/drain counts, tracked actions under
    revert watch, and the bounded action history. Never collective."""
    from multiverso_tpu import policy
    return policy.actions_report()


def MV_PolicyKill() -> None:
    """Runtime kill switch: flip ``-mv_policy`` off. The plane keeps
    watching (sustain/burn state stays warm) but installs nothing from
    the next evaluation on — including actions ALREADY STAGED: the
    pull rendezvous agrees the kill verdict across ranks, so one
    disarmed rank vetoes the whole batch world-wide (it is discarded
    everywhere, never half-installed). Re-arm with
    ``MV_SetFlag('mv_policy', 'true')``."""
    SetCMDFlag("mv_policy", "false")
    Log.Info("policy: kill switch thrown — acting disabled "
             "(MV_SetFlag('mv_policy','true') re-arms)")


def MV_DumpDiagnostics(dir_path: Optional[str] = None) -> Optional[str]:
    """Write the complete postmortem artifact set — flight ring
    (``flight_rank<R>.jsonl``), local telemetry snapshot
    (``telemetry_rank<R>.json``) and span trace
    (``trace_rank<R>.json``) — under ``dir_path`` (default: the
    ``-mv_diag_dir`` flag). With the flag set, failure paths and
    ``Zoo.Stop`` produce the same layout automatically, so one flag
    captures everything a postmortem needs. Returns the directory, or
    None when no directory is configured."""
    from multiverso_tpu.telemetry.ops import dump_diagnostics
    return dump_diagnostics(dir_path)
