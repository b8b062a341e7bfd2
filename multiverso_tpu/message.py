"""Control-plane messages.

Behavioral equivalent of reference include/multiverso/message.h: a message
carries (src, dst, type, table_id, msg_id) plus payload. The reference packs
these into an 8-int header + Blob list for the MPI/ZMQ wire
(message.h:26-66); in the TPU build the data plane is jax arrays in HBM, so
messages are in-process records routed between actors. The ``MsgType``
numeric values are preserved (message.h:13-24) — including the sign/range
routing convention (positive 1..31 = to server, negative = replies to
worker, >32 = controller; reference communicator.cpp:15-27) — so the native
C++ runtime and any future cross-host wire stay compatible.
"""

from __future__ import annotations

import enum
import itertools
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import numpy as np

from multiverso_tpu.utils.waiter import Waiter


class MsgType(enum.IntEnum):
    """Numeric values mirror reference message.h:13-24."""

    Request_Get = 1
    Request_Add = 2
    # batched verb envelope (round 19, no reference equivalent — the
    # value extends the to-server range): payload["members"] carries N
    # pre-built Request_Get/Request_Add messages that enter the engine
    # window in list order via ONE mailbox hop. The envelope itself is
    # never a verb-stream position — the engine flattens it at window
    # drain (sync/server.py _expand_multi), so the members are ordinary
    # verbs to every downstream layer (dedup, chaos, windows, replies).
    Request_MultiVerb = 5
    Request_Barrier = 33
    Request_Register = 34
    # table persistence rides the server mailbox so snapshots are ordered
    # against every applied Add (native kStoreTable/kLoadTable = 34/35;
    # 34 is taken here by Request_Register, so one shared type carries
    # both directions in its payload)
    Request_StoreLoad = 35
    # serving-plane snapshot publish (serving/snapshot.py): rides the
    # server mailbox/window stream as a BARRIER, exactly like
    # Request_StoreLoad — every SPMD rank dispatches it at the same
    # stream position, which is what makes the published version a
    # cross-table-consistent cut (no reference equivalent; the value
    # extends the reference's table-persistence range)
    Request_Publish = 36
    Reply_Get = -1
    Reply_Add = -2
    Reply_Barrier = -33
    Reply_Register = -34
    Server_Finish_Train = 4
    Control_Reply_Finish_Train = -36
    Default = 0


def to_server(t: MsgType) -> bool:
    return 0 < int(t) < 32


def to_worker(t: MsgType) -> bool:
    return -32 < int(t) < 0


def to_controller(t: MsgType) -> bool:
    return int(t) > 32


def _map_arrays(fn, result):
    """``result`` with ``fn`` applied to every array leaf of its tuples
    and lists; non-array leaves are shared."""
    if isinstance(result, np.ndarray):
        return fn(result)
    if isinstance(result, tuple):
        return tuple(_map_arrays(fn, r) for r in result)
    if isinstance(result, list):
        return [_map_arrays(fn, r) for r in result]
    return result


def copy_result(result):
    """Fresh buffers for a result served to more than one owner — a
    deduped Get's extra repliers (sync/server.py) or a worker-side
    cache hit (tables/base.py): callers own and may mutate their
    result arrays, so every extra serving gets copies."""
    return _map_arrays(np.ndarray.copy, result)


def own_result(result):
    """``result`` with every read-only array leaf replaced by a copy:
    what a server hands its FIRST owner may be a view of a buffer it
    does not own (``np.asarray`` of a device array is read-only), while
    the contract is that a caller owns and may mutate its result.
    Writable leaves pass through untouched, so a result that is already
    the caller's costs nothing."""
    return _map_arrays(
        lambda a: a if a.flags.writeable else a.copy(), result)


_msg_id_counter = itertools.count(1)
_msg_id_lock = threading.Lock()


def next_msg_id() -> int:
    with _msg_id_lock:
        return next(_msg_id_counter)


#: shared first-reply-wins gate (see Message.reply for why shared)
_reply_lock = threading.Lock()


@dataclass
class Message:
    msg_type: MsgType = MsgType.Default
    table_id: int = -1
    msg_id: int = 0
    src: int = 0          # worker_id of the requester (in-process world)
    dst: int = 0
    payload: Dict[str, Any] = field(default_factory=dict)
    # In-process reply channel: the server engine fulfils the request by
    # storing the result and notifying the waiter — the collapsed version of
    # reply-Message -> Communicator -> Worker::ProcessReplyGet
    # (reference worker.cpp:81-91).
    waiter: Optional[Waiter] = None
    result: Any = None
    on_reply: Optional[Callable[["Message"], None]] = None
    #: telemetry (telemetry/trace.py): the sender's span context — the
    #: actor that dequeues this message parents its dispatch span here,
    #: so one span tree follows the verb across the mailbox hop.
    trace_ctx: Any = None
    #: telemetry: enqueue timestamp (time.perf_counter seconds), set by
    #: Actor.Receive; zeroed once the queue-wait has been observed.
    _enq_t: float = 0.0
    _replied: bool = False

    def reply(self, result: Any = None) -> None:
        """First reply wins; later replies (e.g. an engine-level error after
        a successful table reply) are dropped so a request's outcome can't be
        rewritten or its waiter over-notified. The check-and-set rides a
        (module-shared) lock: the engine thread's normal reply races the
        worker-side poison sweep (``Actor._fail_pending`` runs on whichever
        thread pushed last when the loop is dying), and an unlocked
        check-then-act could deliver BOTH replies — rewriting the result
        after a waiter woke, or over-notifying the waiter (found by mvlint
        cross-domain-state). One shared lock, not per-message: the guarded
        region is two attribute stores, so contention is nil, and the verb
        hot path skips a Lock allocation per Message."""
        with _reply_lock:
            if self._replied:
                return
            self._replied = True
            self.result = result
        if self.on_reply is not None:
            self.on_reply(self)
        if self.waiter is not None:
            self.waiter.Notify()
