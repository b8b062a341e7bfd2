"""Consistent versioned snapshots: the ``Publish`` cut + table captures.

**The cut.** ``publish()`` sends ONE ``Request_Publish`` message through
the engine mailbox. The windowed engine treats every non-Get/Add message
as a window BARRIER (sync/server.py ``_local_window`` /
``_ExchangeStage``): windows split around it, and in a multi-process
world the head-marker exchange proves every SPMD rank dispatches it at
the SAME window-stream position (a diverged rank trips the loud CHECK).
The capture callback therefore runs on the engine thread with every Add
admitted before the cut applied and none after — on every rank, for
every table at once. That is the whole consistency argument: the cut
inherits the engine stream's already-proven lockstep order instead of
inventing a second quiesce mechanism. ``MV_SaveCheckpoint`` rides the
SAME mechanism (checkpoint.py), so the two cuts cannot drift.

**Zero-copy where the storage layout allows it.** A snapshot must
outlive arbitrary later training, but the engine's jit'd updates DONATE
their input buffers (``donate_argnums``) — holding a bare reference to
``state['data']`` would dangle after the very next Add. So "zero-copy"
is bounded by donation: a device-resident capture takes ONE on-device
``jnp.copy`` (no host crossing, no transfer of anything but the version
stamp afterwards) and serves lookups from that immutable array through
the table's own jit'd row gather (ops.rows / pallas_rows on TPU); host
mirrors and logical materializations are copy-on-publish numpy. Either
way the snapshot is immutable after install, which is what makes
concurrent lock-free reads sound.

**Values match training Gets.** Captures go through the same read paths
a training Get uses — ``_full_logical`` / ``_gather_rows``, both of
which apply the updater's ``access()`` transform — so a served row is
bit-identical to what ``GetRows`` at the cut position would have
returned.

Residence is picked per table by ``-mv_serving_residence``:

* ``host`` — logical numpy at publish (copy-on-publish). Multi-process
  worlds ALWAYS use host residence: a serving thread must never issue
  device programs that could interleave with the engine's collectives
  in rank-divergent order (the capture itself may run collectives — it
  executes inside the lockstep barrier dispatch, where they are
  matched).
* ``device`` — one on-device copy + per-tick fused gathers
  (single-process only; the right choice on a real accelerator where
  the table does not fit host RAM or the host hop dominates).
* ``auto`` — device on an accelerator backend when legal, host
  otherwise (on the CPU backend a numpy row gather beats a jit
  dispatch per tick).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Dict

import numpy as np

from multiverso_tpu.message import MsgType
from multiverso_tpu.telemetry import metrics as tmetrics
from multiverso_tpu.utils.configure import GetFlag
from multiverso_tpu.utils.log import CHECK, Log


def residence_mode() -> str:
    mode = str(GetFlag("mv_serving_residence")).lower()
    CHECK(mode in ("auto", "host", "device"),
          f"-mv_serving_residence must be auto/host/device, got {mode!r}")
    return mode


class TableSnapshot:
    """One table's immutable published state. Subclasses implement the
    union read; the front-end slices per caller. ``dispatches`` counts
    fused union gathers actually issued — the micro-batch coalescing
    tests assert ONE per tick however many callers rode it. The count
    rides a lock: the dispatcher thread, a synchronous caller winning
    the inline-combiner lock, the replica serve threads and the fan-out
    encoder all read the SAME published snapshot concurrently, and a
    bare ``+=`` loses increments exactly when the oracle matters
    (found by mvlint cross-domain-state)."""

    def __init__(self):
        self.dispatches = 0
        self._disp_lock = threading.Lock()

    def _count_dispatch(self) -> None:
        with self._disp_lock:
            self.dispatches += 1

    def nbytes(self) -> int:
        raise NotImplementedError

    def lookup_union(self, union_ids: np.ndarray) -> np.ndarray:
        """Rows/values for a sorted unique id vector in ONE dispatch."""
        raise NotImplementedError

    def full(self) -> np.ndarray:
        """The whole logical table (fresh copy — the caller owns it)."""
        raise NotImplementedError

    def validate_ids(self, ids: np.ndarray) -> None:
        """Raise on out-of-domain ids BEFORE the request joins a
        micro-batch (one bad caller must not fail the shared gather)."""


class MatrixSnapshot(TableSnapshot):
    """Row-addressed snapshot (matrix / sparse-matrix families)."""

    def __init__(self, num_rows: int, num_cols: int, *, rows=None,
                 dev=None):
        super().__init__()
        self.num_rows = num_rows
        self.num_cols = num_cols
        self._rows = rows          # host residence: (num_rows, num_cols)
        self._dev = dev            # device residence: (data, aux, gather)

    @classmethod
    def host(cls, rows: np.ndarray):
        rows = np.ascontiguousarray(rows)
        return cls(rows.shape[0], rows.shape[1], rows=rows)

    @classmethod
    def device(cls, data, aux, gather, pad_ids, num_rows: int,
               num_cols: int):
        """``data`` is the one-jnp.copy immutable storage; ``gather`` is
        the table's jit'd row gather (pure fn of (data, aux, padded
        ids) — ops.rows/pallas_rows inside); ``pad_ids`` pads an id
        batch to its power-of-two bucket."""
        return cls(num_rows, num_cols,
                   dev=(data, aux, gather, pad_ids))

    def nbytes(self) -> int:
        if self._rows is not None:
            return int(self._rows.nbytes)
        return int(self._dev[0].nbytes)

    def validate_ids(self, ids: np.ndarray) -> None:
        if ids.size == 0:
            raise ValueError("empty row id set")
        if int(ids.min()) < 0 or int(ids.max()) >= self.num_rows:
            raise ValueError(
                f"row id out of range [0, {self.num_rows})")

    def lookup_union(self, union_ids: np.ndarray) -> np.ndarray:
        self._count_dispatch()
        if self._rows is not None:
            return self._rows[union_ids]
        data, aux, gather, pad_ids = self._dev
        rows = gather(data, aux, pad_ids(union_ids))
        return np.asarray(rows[: len(union_ids), : self.num_cols])

    def full(self) -> np.ndarray:
        if self._rows is not None:
            self._count_dispatch()
            return self._rows.copy()
        # device path: lookup_union counts the one gather it issues.
        # np.array(copy=True): np.asarray of a jax array can be a
        # READ-ONLY zero-copy view (CPU backend) — full() promises a
        # caller-owned writable array (id lookups get theirs from the
        # frontend's per-caller fancy-index slice)
        return np.array(self.lookup_union(
            np.arange(self.num_rows, dtype=np.int32)))


class VectorSnapshot(TableSnapshot):
    """Whole-vector snapshot (array family): lookups index elements."""

    def __init__(self, values: np.ndarray):
        super().__init__()
        self._values = np.ascontiguousarray(values)

    def nbytes(self) -> int:
        return int(self._values.nbytes)

    def validate_ids(self, ids: np.ndarray) -> None:
        if ids.size == 0:
            raise ValueError("empty id set")
        if int(ids.min()) < 0 or int(ids.max()) >= self._values.size:
            raise ValueError(
                f"index out of range [0, {self._values.size})")

    def lookup_union(self, union_ids: np.ndarray) -> np.ndarray:
        self._count_dispatch()
        return self._values[union_ids]

    def full(self) -> np.ndarray:
        self._count_dispatch()
        return self._values.copy()


class KVSnapshot(TableSnapshot):
    """Key-addressed snapshot: sorted int64 keys + aligned values;
    absent keys read as 0 (the live table's own Get contract)."""

    def __init__(self, keys: np.ndarray, values: np.ndarray):
        super().__init__()
        order = np.argsort(keys, kind="stable")
        self._keys = np.ascontiguousarray(keys[order])
        self._values = np.ascontiguousarray(values[order])

    def nbytes(self) -> int:
        return int(self._keys.nbytes + self._values.nbytes)

    def validate_ids(self, ids: np.ndarray) -> None:
        if ids.size == 0:
            raise ValueError("empty key set")

    def lookup_union(self, union_keys: np.ndarray) -> np.ndarray:
        self._count_dispatch()
        if not len(self._keys):
            return np.zeros(len(union_keys), self._values.dtype)
        pos = np.searchsorted(self._keys, union_keys)
        pos_c = np.minimum(pos, len(self._keys) - 1)
        hit = self._keys[pos_c] == union_keys
        out = np.where(hit, self._values[pos_c], 0)
        return out.astype(self._values.dtype, copy=False)

    def full(self) -> np.ndarray:
        # "everything" for a KV table is its value vector in sorted-key
        # order; pair it with items() for the keys
        self._count_dispatch()
        return self._values.copy()

    def items(self):
        """(sorted keys, aligned values) — both immutable views."""
        return self._keys, self._values


@dataclass
class Snapshot:
    """One published version: every exported table at one cut."""

    version: int
    created_wall: float
    window_epoch: int
    tables: Dict[int, TableSnapshot] = field(default_factory=dict)

    def age_s(self) -> float:
        return max(0.0, time.time() - self.created_wall)

    def nbytes(self) -> int:
        return sum(t.nbytes() for t in self.tables.values())


def _capture_all(engine, store) -> Snapshot:
    """Runs ON the engine thread inside the Publish barrier dispatch:
    every table's export at one stream position = one consistent cut."""
    t0 = time.perf_counter()
    tables: Dict[int, TableSnapshot] = {}
    for tid, table in enumerate(engine.store_):
        export = getattr(table, "serving_export", None)
        if export is None:
            continue
        ts = export()
        if ts is not None:
            tables[tid] = ts
    snap = Snapshot(version=store.alloc_version(),
                    created_wall=time.time(),
                    # cross-stream position: total windows applied
                    # over every engine shard stream (round 12)
                    window_epoch=engine.cut_epoch(),
                    tables=tables)
    store.install(snap)
    # replica plane fan-out hook (round 17): drain the per-table
    # publish journals AT this fenced stream position (that is the
    # delta-soundness argument — every Add admitted before the cut
    # marked its journal before this drain, none after) and kick the
    # fan-out thread. Local numpy only; one attribute read when off.
    try:
        from multiverso_tpu import replica as _replica
        _replica.note_publish(engine, snap)
    except Exception as exc:    # fan-out must never fail a publish
        Log.Error("replica fan-out publish hook failed: %r", exc)
    tmetrics.gauge("serving.snapshot_bytes").set(snap.nbytes())
    tmetrics.gauge("serving.snapshot_age_s").set(0.0)
    tmetrics.histogram("serving.publish_s").observe(
        time.perf_counter() - t0)
    Log.Debug("serving: published snapshot v%d (%d tables, %d bytes)",
              snap.version, len(tables), snap.nbytes())
    return snap


def publish(zoo=None) -> int:
    """Publish a consistent versioned snapshot of every live table;
    returns the new version. COLLECTIVE in a multi-process world (every
    process calls it at the same verb-stream position, like MV_Barrier —
    the head-marker exchange CHECK-fails a diverged program). Bounded by
    ``-mv_deadline_s`` when set."""
    from multiverso_tpu.serving import get_plane
    from multiverso_tpu.zoo import Zoo
    zoo = zoo or Zoo.Get()
    plane = get_plane()

    def _cut():
        return _capture_all(zoo.server_engine, plane.store).version

    return zoo.CallOnEngine(MsgType.Request_Publish, _cut,
                            "snapshot publish (MV_PublishSnapshot)")
