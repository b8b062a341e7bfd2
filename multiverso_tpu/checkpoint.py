"""Framework-level checkpoint/resume of all server tables.

The reference has no checkpoint *driver* — ``Serializable::Store/Load``
exists on each server table (table_interface.h:61-70) but only apps call it,
one table at a time, data only (SURVEY.md §5 "checkpoint/resume"). This
module adds the TPU-native equivalent SURVEY.md §5 prescribes: one call
saves every registered server table *plus its updater aux state* (the
reference loses AdaGrad accumulators and momentum smoothing on restart —
a training run resumed from a reference checkpoint silently restarts its
second-moment estimates; here resume is exact).

Format (all through the URI-dispatched Stream layer, utils/io.py, so
anything the IO layer can address — local file now, other schemes when
registered — can hold a checkpoint):

    magic "MVTCKPT1", num_tables
    per table: table_id, type name, length-framed Store() payload,
               num aux leaves, per leaf: keypath, dtype, shape, bytes

Sharded device arrays — data AND aux — are serialized in *logical* layout
(tables expose ``aux_to_logical``/``aux_from_logical`` to strip their
padding/interleaving) and re-placed with each table's live sharding on
load, so the checkpoint is layout-independent: a job may resume on a
different mesh size (the reference's per-server shard files cannot).
Frames are verified on load: table type, full payload consumption (catches
dtype/config drift), aux leaf shapes and dtypes.
"""

from __future__ import annotations

import io as _io
from typing import Optional

import jax
import numpy as np

from multiverso_tpu.utils.io import Stream, StreamFactory
from multiverso_tpu.utils.log import CHECK, Log

_MAGIC = "MVTCKPT1"


def _aux_leaves(table):
    state = getattr(table, "state", None)
    if not isinstance(state, dict) or "aux" not in state:
        return []
    leaves = jax.tree_util.tree_leaves_with_path(state["aux"])
    return [(jax.tree_util.keystr(path), leaf) for path, leaf in leaves]


def _to_logical(table, keypath: str, leaf) -> np.ndarray:
    """Aux leaf in mesh-independent (logical) layout when the table knows
    how; raw host layout otherwise."""
    if hasattr(table, "aux_to_logical"):
        return table.aux_to_logical(keypath, leaf)
    return np.asarray(leaf)


def _from_logical(table, keypath: str, arr: np.ndarray) -> np.ndarray:
    if hasattr(table, "aux_from_logical"):
        return table.aux_from_logical(keypath, arr)
    return arr


def _write_table(stream: Stream, table_id: int, table) -> None:
    stream.WriteInt(table_id)
    stream.WriteStr(type(table).__name__)
    buf = _io.BytesIO()
    table.Store(Stream(buf, f"<table {table_id}>"))
    payload = buf.getvalue()
    stream.WriteInt(len(payload))
    stream.Write(payload)
    leaves = _aux_leaves(table)
    stream.WriteInt(len(leaves))
    for keypath, leaf in leaves:
        host = _to_logical(table, keypath, leaf)
        stream.WriteStr(keypath)
        stream.WriteStr(str(host.dtype))
        stream.WriteInt(host.ndim)
        for d in host.shape:
            stream.WriteInt(d)
        stream.Write(np.ascontiguousarray(host).tobytes())


def _read_table(stream: Stream, table) -> None:
    type_name = stream.ReadStr()
    CHECK(type_name == type(table).__name__,
          f"checkpoint table type mismatch: {type_name} vs "
          f"{type(table).__name__}")
    payload_len = stream.ReadInt()
    payload = stream.Read(payload_len)
    payload_stream = Stream(_io.BytesIO(payload), "<table payload>")
    table.Load(payload_stream)
    CHECK(payload_stream._f.tell() == payload_len,
          f"table {type_name} consumed {payload_stream._f.tell()} of "
          f"{payload_len} checkpoint bytes — dtype/config drift")
    n_leaves = stream.ReadInt()
    if n_leaves == 0:
        return
    live = dict(_aux_leaves(table))
    restored = {}
    for _ in range(n_leaves):
        keypath = stream.ReadStr()
        dtype = np.dtype(stream.ReadStr())
        ndim = stream.ReadInt()
        shape = tuple(stream.ReadInt() for _ in range(ndim))
        raw = stream.Read(int(np.prod(shape)) * dtype.itemsize if shape
                          else dtype.itemsize)
        arr = np.frombuffer(raw, dtype).reshape(shape)
        CHECK(keypath in live, f"unknown aux leaf {keypath} in checkpoint")
        live_logical = _to_logical(table, keypath, live[keypath])
        CHECK(live_logical.shape == arr.shape,
              f"aux leaf {keypath} shape mismatch: checkpoint {arr.shape} "
              f"vs live {live_logical.shape}")
        CHECK(live_logical.dtype == arr.dtype,
              f"aux leaf {keypath} dtype mismatch: checkpoint {arr.dtype} "
              f"vs live {live_logical.dtype}")
        restored[keypath] = _from_logical(table, keypath, arr)
    # re-place every restored leaf with the table's live sharding
    def replace(path, leaf):
        key = jax.tree_util.keystr(path)
        if key in restored:
            return jax.device_put(restored[key], leaf.sharding)
        return leaf
    table.state = dict(table.state)
    table.state["aux"] = jax.tree_util.tree_map_with_path(
        replace, table.state["aux"])


def write_table_frame(table, table_id: int = 0) -> bytes:
    """ONE table's complete logical state (Store payload + updater aux
    leaves in mesh-independent layout) as a self-contained byte frame —
    the unit the elastic plane captures at a cut, splits into row
    shards for the move wire, and restores from on an epoch's new mesh
    (elastic/rebalance.py). Same format as one table's slice of a
    checkpoint file, so the two serializations cannot drift."""
    buf = _io.BytesIO()
    _write_table(Stream(buf, f"<frame {table_id}>"), table_id, table)
    return buf.getvalue()


def read_table_frame(table, blob: bytes) -> None:
    """Restore ``table`` from a :func:`write_table_frame` blob. The
    table's live mesh/sharding may differ from the writer's — values
    and aux re-place with the live shardings, exactly like a checkpoint
    load onto a different mesh size."""
    stream = Stream(_io.BytesIO(blob), "<frame>")
    stream.ReadInt()                    # table_id (caller's bookkeeping)
    _read_table(stream, table)


def _quiesce(zoo) -> None:
    """Drain the engine mailbox, then (multihost) barrier: no in-flight
    async Add may still be issuing collectives on any process's engine
    thread when checkpoint fetches start issuing theirs on the caller
    thread — interleaved collectives across threads would mismatch across
    processes. Also makes the checkpoint consistent with every Add
    enqueued before the call, single-process included. Concurrent Adds
    *during* a checkpoint violate the collective contract (don't)."""
    from multiverso_tpu.parallel import multihost
    zoo.DrainServer()
    multihost.host_barrier("mv_checkpoint_quiesce")


def _write_all(stream: Stream, tables) -> None:
    stream.WriteStr(_MAGIC)
    stream.WriteInt(len(tables))
    for table_id, table in enumerate(tables):
        _write_table(stream, table_id, table)


def _serialize_to_uri(uri: str, tables) -> int:
    """Serialize every table: rank 0 streams to storage, other ranks
    into a throwaway sink purely to drive their half of the collective
    fetches (the reference's rank-0-saves convention,
    distributed_wordembedding.cpp:263-306)."""
    from multiverso_tpu.parallel import multihost
    if multihost.process_index() == 0:
        # stream straight to storage: O(largest frame) host memory
        with StreamFactory.GetStream(uri, "w") as stream:
            _write_all(stream, tables)
    else:
        _write_all(Stream(_io.BytesIO(), uri), tables)
    return len(tables)


def _serialize_to_bytes(uri: str, tables) -> bytes:
    """In-memory serialization for the engine-thread cut: the engine
    must never run the URI IO (possibly slow remote storage) — only the
    in-memory serialize occupies it, exactly the native bridge's
    Store/Load rule (binding/native_bridge.py). Rank 0 returns the
    bytes (the caller streams them out); other ranks return b"" after
    driving their half of the collective fetches. Costs O(total
    checkpoint bytes) of host memory on rank 0 — the price of keeping
    slow storage off the verb stream."""
    from multiverso_tpu.parallel import multihost
    buf = _io.BytesIO()
    _write_all(Stream(buf, uri), tables)
    return buf.getvalue() if multihost.process_index() == 0 else b""


def save_checkpoint(uri: str, zoo=None) -> int:
    """Store every registered server table (+ updater aux) to ``uri``.
    Returns the number of tables written.

    CONSISTENT CUT (round 8): the serialization runs ON the engine
    thread as a window-stream barrier message — the SAME mechanism a
    serving ``MV_PublishSnapshot`` cuts with (serving/snapshot.py), so
    the two cut paths cannot drift: a checkpoint taken back-to-back
    with a publish at one stream position serializes bit-identical
    values (tests/test_serving.py parity test). This replaces the old
    bespoke DrainServer+host_barrier quiesce for the save cut: every
    Add admitted before this message is applied first (engine FIFO /
    lockstep barrier position), none after, and in a multi-process
    world the head-marker exchange proves every rank cuts at the same
    position — so the serialization's collective fetches are matched
    by construction instead of by a separate quiesce round.

    Collective in a multi-process job: every process calls it at the
    same verb-stream position; only process 0 streams to the file, and
    a barrier makes the file complete before anyone proceeds. ``uri``
    must name shared storage for a later multi-process load."""
    from multiverso_tpu.message import MsgType
    from multiverso_tpu.parallel import multihost
    from multiverso_tpu.zoo import Zoo
    zoo = zoo or Zoo.Get()
    tables = zoo.server_tables
    if zoo.server_engine is None:
        # -ma mode / no engine: nothing is in flight — serialize on the
        # caller thread behind a plain alignment barrier
        multihost.host_barrier("mv_checkpoint_quiesce")
        n = _serialize_to_uri(uri, tables)
    else:
        # the CUT (in-memory serialize, collective fetches included)
        # runs on the engine thread; the URI IO stays on THIS thread so
        # slow remote storage never blocks the verb stream behind the
        # barrier (and never turns -mv_deadline_s into spurious worker
        # deadline failures during an upload)
        payload = zoo.CallOnEngine(MsgType.Request_StoreLoad,
                                   lambda: _serialize_to_bytes(uri, tables),
                                   "checkpoint save cut")
        if multihost.process_index() == 0:
            with StreamFactory.GetStream(uri, "w") as stream:
                stream.Write(payload)
        n = len(tables)
    multihost.host_barrier("mv_checkpoint_save")
    Log.Info("checkpoint: saved %d tables to %s", n, uri)
    return n


def load_checkpoint(uri: str, zoo=None) -> int:
    """Restore every registered server table from ``uri``. The same tables
    (count, order, shapes) must already be registered — mesh size may
    differ (re-placement uses the live shardings)."""
    from multiverso_tpu.zoo import Zoo
    zoo = zoo or Zoo.Get()
    tables = zoo.server_tables
    _quiesce(zoo)
    with StreamFactory.GetStream(uri, "r") as stream:
        CHECK(stream.ReadStr() == _MAGIC, "not a multiverso_tpu checkpoint")
        n = stream.ReadInt()
        CHECK(n == len(tables),
              f"checkpoint has {n} tables, registry has {len(tables)}")
        for _ in range(n):
            table_id = stream.ReadInt()
            CHECK(0 <= table_id < len(tables), "bad table id in checkpoint")
            _read_table(stream, tables[table_id])
    Log.Info("checkpoint: restored %d tables from %s", n, uri)
    return n
