"""Actor runtime: one thread + mailbox + per-MsgType handler map.

Behavioral equivalent of reference include/multiverso/actor.h:18-57 /
src/actor.cpp: an actor owns an ``MtQueue`` mailbox and a thread running a
dispatch loop over registered handlers. Actor names match the reference
constants (actor.h:60-66).

TPU note: the reference needs four actors per process (communicator,
controller, server, worker) because shards live in per-process heaps behind
a network. Here only the *server engine* is an actor — it serializes
Get/Add application onto the mesh-sharded store, which is exactly the
single-writer discipline the reference's server mailbox provided. Worker-side
request fan-out and the communicator collapse into direct mailbox pushes
(documented in docs/DESIGN.md). The base class is still generic and is also
exercised standalone in tests for parity.
"""

from __future__ import annotations

import threading
import time
import traceback
from typing import Callable, Dict, Optional

from multiverso_tpu.failsafe import chaos
from multiverso_tpu.failsafe.deadline import (DEFAULT_SHUTDOWN_JOIN_S,
                                              deadline_s)
from multiverso_tpu.failsafe.errors import ActorDied
from multiverso_tpu.message import Message, MsgType
from multiverso_tpu.telemetry import flight, metrics, trace
from multiverso_tpu.utils.log import CHECK, Log
from multiverso_tpu.utils.mt_queue import MtQueue


class actor_names:
    """reference actor.h:60-66."""

    kCommunicator = "communicator"
    kController = "controller"
    kServer = "server"
    kWorker = "worker"


class Actor:
    def __init__(self, name: str):
        self.name = name
        self.mailbox: MtQueue[Message] = MtQueue()
        self._handlers: Dict[MsgType, Callable[[Message], None]] = {}
        self._thread: Optional[threading.Thread] = None
        self._started = threading.Event()
        #: fail-fast poison: set to the original exception when the
        #: loop thread dies; Receive then raises ActorDied immediately
        #: instead of enqueueing into a dead thread
        self._poison: Optional[BaseException] = None
        self._current_msg: Optional[Message] = None
        # telemetry: mailbox backlog + how long messages sat in it
        # (queue-wait is the actor-side half of a verb's latency; the
        # other half is the handler span). NULL instruments when off.
        self._m_depth = metrics.gauge(f"actor.{name}.mailbox_depth")
        self._m_qwait = metrics.histogram(f"actor.{name}.queue_wait_s")
        self._m_received = metrics.counter(f"actor.{name}.messages")
        self._span_name = f"actor.{name}.dispatch"

    def RegisterHandler(self, msg_type: MsgType, handler: Callable[[Message], None]) -> None:
        self._handlers[msg_type] = handler

    def Start(self) -> None:
        self._thread = threading.Thread(target=self._main, name=f"mv-{self.name}",
                                        daemon=True)
        self._thread.start()
        ok = self._started.wait(60.0)  # reference busy-wait handshake
        # (actor.cpp:24-26), done with an event instead of spinning
        # (SURVEY.md flags the spin as a smell not to copy). Bounded:
        # a thread that never reaches its loop is a broken interpreter,
        # not something to block startup on forever.
        CHECK(ok, f"actor {self.name} thread failed to start in 60s")

    def Stop(self) -> None:
        """Drain + join, BOUNDED: a stuck actor (handler wedged in a
        device op or an abandoned collective) is logged with its name
        and queue depth instead of hanging MV_ShutDown. The bound is
        -mv_deadline_s when set, else a generous shutdown default —
        opting into deadlines deliberately bounds shutdown too, which
        can abandon a legitimately slow final handler: the daemon
        thread still runs to completion unless the process exits first,
        and the Log.Error below is the audit trail either way."""
        self.mailbox.Exit()
        if self._thread is not None:
            self._thread.join(deadline_s() or DEFAULT_SHUTDOWN_JOIN_S)
            if self._thread.is_alive():
                Log.Error(
                    "actor %s stuck at shutdown (mailbox depth %d) — "
                    "abandoning its daemon thread", self.name,
                    self.mailbox.Size())
            self._thread = None

    def Receive(self, msg: Message) -> None:
        """Push into the mailbox (reference actor.h:45-47). Raises
        ``ActorDied`` (original traceback chained) when the loop thread
        is dead — fail fast, never enqueue into a dead thread. Chaos
        (when armed) may drop/duplicate/delay table verbs here."""
        if self._poison is not None:
            raise ActorDied(self.name, self._poison) from self._poison
        cz = chaos.get()
        if (cz is not None
                and msg.msg_type in (MsgType.Request_Get,
                                     MsgType.Request_Add)
                and not getattr(msg, "_fs_chaos_done", False)):
            # one decision per first delivery: redeliveries and dups
            # must not roll the dice again (schedules stay lockstep
            # across SPMD ranks running the same verb program)
            msg._fs_chaos_done = True
            action = cz.mailbox_action()
            if action == "dup":
                self._push(msg)       # same object twice: the engine's
                self._push(msg)       # dedup window skips the copy
                return
            if action in ("drop", "delay"):
                chaos.schedule_redelivery(self._push, msg, action,
                                          cz.param(f"mailbox.{action}"))
                return
        self._push(msg)

    def _push(self, msg: Message) -> None:
        msg._enq_t = time.perf_counter()
        self.mailbox.Push(msg)
        self._m_received.inc()
        self._m_depth.set(self.mailbox.Size())
        if self._poison is not None:
            # lost race with a dying loop thread: its drain may have
            # missed this message — fail whatever is still queued
            self._fail_pending(self._poison)

    def note_dequeue(self, msg: Message) -> None:
        """Telemetry at the moment a message leaves the mailbox: observe
        its queue wait, refresh the depth gauge (Receive alone would
        leave it a stale high-water mark once the backlog drains), and
        close the flow arrow. Idempotent per message (engines drain
        windows with TryPop and then pass the head back through
        _dispatch — only the first sighting counts)."""
        if msg._enq_t:
            self._m_qwait.observe(time.perf_counter() - msg._enq_t)
            msg._enq_t = 0.0
            self._m_depth.set(self.mailbox.Size())
            trace.flow_end(msg.trace_ctx)

    def _dispatch(self, msg: Message) -> None:
        """Route one message through its handler; failures reply to the
        caller's Wait() instead of killing the loop. Shared by the main
        loop and engines that drain extra messages (pipeline windows)."""
        self.note_dequeue(msg)  # before the unhandled bail-out too, or
        # the depth gauge sticks at its high-water mark
        handler = self._handlers.get(msg.msg_type)
        if handler is None:
            Log.Error("actor %s: unhandled message type %s", self.name,
                      msg.msg_type)
            return
        # args built only when tracing is on — this is the one span
        # entry on the per-message hot path (the -trace-off default
        # must stay allocation-free)
        with trace.span(self._span_name, cat="actor",
                        parent=msg.trace_ctx,
                        args=({"msg_type": int(msg.msg_type)}
                              if trace.enabled() else None)):
            try:
                handler(msg)
            except Exception as exc:  # surface, don't kill the loop silently
                Log.Error("actor %s: handler for %s raised: %r", self.name,
                          msg.msg_type, exc)
                # route through the normal reply path so the error reaches
                # the caller's Wait() and re-raises there
                msg.reply(exc)
                if getattr(exc, "mv_fatal", False):
                    # e.g. a DeadlineExceeded that abandoned a
                    # collective: this actor's stream is unsound —
                    # poison instead of processing more messages
                    raise

    def _fail_pending(self, original: BaseException) -> None:
        """Fail every queued (and the in-dispatch) message with the
        poison error so their waiters raise instead of hanging."""
        died = ActorDied(self.name, original)
        died.__cause__ = original
        cur = self._current_msg
        if cur is not None:
            cur.reply(died)     # no-op if it already replied
        while True:
            ok, m = self.mailbox.TryPop()
            if not ok:
                return
            m.reply(died)

    def _main(self) -> None:
        trace.name_native_thread()
        self._started.set()
        try:
            while True:
                ok, msg = self.mailbox.Pop()
                if not ok:
                    break
                self._current_msg = msg
                self._dispatch(msg)
                self._current_msg = None
        except BaseException as exc:
            # fail-fast actor death: record the poison FIRST (Receive
            # checks it before pushing), then fail everything queued —
            # subsequent Receive/Wait re-raise the original traceback
            # immediately instead of feeding a dead thread
            self._poison = exc
            metrics.counter(f"actor.{self.name}.deaths").inc()
            flight.record("actor.poison",
                          detail=f"{self.name}: {type(exc).__name__}")
            Log.Error("actor %s: loop thread died, poisoning mailbox:\n%s",
                      self.name, traceback.format_exc())
            self.mailbox.Exit()
            self._fail_pending(exc)
