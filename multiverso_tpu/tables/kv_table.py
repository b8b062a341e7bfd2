"""KVTable — distributed hash map of scalar values keyed by int64.

Behavioral equivalent of reference include/multiverso/table/kv_table.h
(header-only): keys hash to servers by ``key % num_servers``
(kv_table.h:49), the server-side Add is plain ``+=`` (kv_table.h:82-112 —
KV does NOT route through the updater stack), Get returns current values
(missing keys read as 0), and the worker keeps a local cache exposed via
``raw()`` (kv_table.h:40).

TPU design: control plane / data plane split — the *slot index* (key ->
dense slot) is a host dict (dynamic key sets are host logic; static shapes
stay on device), the *values* are one growable jax array in HBM sharded over
the mesh ``server`` axis. Add = host slot resolution + jit'd scatter-add
(duplicate keys in a batch accumulate natively); Get = jit'd gather with
power-of-two bucketed batch sizes. Capacity doubles amortized on growth.

``Store/Load``: the reference aborts with "Not implemented yet"
(kv_table.h:106-112); here checkpointing IS implemented (keys + values) —
a documented capability improvement.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu.parallel import multihost
from multiverso_tpu.parallel.mesh import (local_device_count, next_bucket,
                                          pad_to_multiple, parts_bucket,
                                          place_parts)
from multiverso_tpu.tables.base import ServerTable, TableOption, WorkerTable
from multiverso_tpu.telemetry import sketch as tsketch
from multiverso_tpu.updaters.base import AddOption, GetOption
from multiverso_tpu.utils.log import CHECK

_MIN_BUCKET = 8


@dataclass
class KVTableOption(TableOption):
    init_capacity: int = 1024
    dtype: type = np.float32

    def make_server(self, zoo):
        return KVServerTable(self.dtype, zoo, self.init_capacity)

    def make_worker(self, zoo):
        return KVWorkerTable(self.dtype)


class KVServerTable(ServerTable):
    #: replica-plane journal granularity (tables/base.py contract):
    #: key-addressed — the fan-out delta ships touched keys' values
    publish_journal_kind = "keys"

    def __init__(self, dtype, zoo, init_capacity: int = 1024):
        self.dtype = np.dtype(dtype)
        self._zoo = zoo
        ctx = zoo.mesh_ctx
        self._sharding = ctx.sharding_1d()
        self.capacity = pad_to_multiple(max(init_capacity, _MIN_BUCKET),
                                        ctx.num_servers)
        self._index: Dict[int, int] = {}
        # control plane, fastest available first: the native int64 hash
        # index (native/src/kv_index.cc — batch-order slot assignment,
        # ~20x the searchsorted cache on 100k-key batches) when the
        # toolchain is present, else the vectorized python lookup below:
        # sorted key/slot arrays serve bulk searchsorted lookups; keys
        # inserted since the last rebuild live in ``_pending`` (consulted
        # only for searchsorted misses), and the sorted arrays rebuild
        # when pending grows past a fraction of the index — so a trickle
        # of new keys never triggers whole-index rebuilds
        self._nat_index = None        # created lazily on first index use
        self._nat_index_tried = False  # (KvIndex.create may build the .so)
        # round 13 — key-access skew sketch (-mv_row_sketch extended
        # from the matrix family: the ROADMAP hot-row-cache groundwork
        # wants skew on BOTH families). Lazy SpaceSaving via
        # telemetry/sketch.note_table_access; off = one cached int read
        # per Get. The /perf row_skew list and Dashboard [RowSkew] line
        # pick these up through the same _row_sketch attribute.
        self._row_sketch = None
        self._row_sketch_notes = 0
        self._sorted_keys = np.empty(0, np.int64)
        self._sorted_slots = np.empty(0, np.int32)
        self._pending: Dict[int, int] = {}
        # 64-bit dtypes (e.g. the WordEmbedding int64 word-count table,
        # reference communicator.cpp:17-33) stay host-resident: jax truncates
        # them to 32 bits without global x64 mode, and scalar counters are
        # control-plane data with no business on the device anyway.
        self._host_backed = self.dtype.itemsize == 8
        # CPU-backend host mirror state (f32 branch only; see _np_values).
        # Initialized before any _values assignment — the property setter
        # below consults these.
        self._values_np = None
        self._np_dirty = False
        self._host_values_ok = False
        if self._host_backed:
            self._values = np.zeros(self.capacity, self.dtype)

            def _scatter_add(values, slots, deltas):
                np.add.at(values, np.asarray(slots), np.asarray(deltas))
                return values

            def _gather(values, slots):
                return values[np.asarray(slots)]

            self._scatter_add = _scatter_add
            self._gather = _gather
            return
        self._values = ctx.place(jnp.zeros((self.capacity,), self.dtype),
                                 self._sharding)
        # CPU-backend host mirror for the f32 values: host verbs apply
        # with numpy at vector speed instead of per-op jit dispatches
        # (~6ms/pair measured); device-plane reads sync pending host
        # writes back, ANY assignment to ``_values`` (the property
        # setter) drops the mirror. A live mirror is ALWAYS fresh;
        # ``_np_dirty`` marks device-side staleness only. Multi-process
        # (round 5): the mirror is REPLICATED per rank — every host verb
        # reaches it as identically merged (keys, deltas) through the
        # windowed engine's parts paths / merge_collective_add, so the
        # replicas evolve in lockstep and Gets serve locally.
        self._host_values_ok = jax.default_backend() == "cpu"

        def _scatter_add(values, slots, deltas):
            return values.at[slots].add(deltas)

        self._scatter_add = jax.jit(_scatter_add, donate_argnums=(0,))

        def _gather(values, slots):
            return values[slots]

        self._gather = jax.jit(_gather)

    # -- CPU host mirror (f32 values) ---------------------------------------

    @property
    def _values(self):
        return self._values_arr

    @_values.setter
    def _values(self, arr) -> None:
        # safety by construction: ANY assignment makes the new array
        # authoritative, so a code path that replaces the values can
        # never leave a stale mirror serving host Gets
        self._values_arr = arr
        self._values_np = None
        self._np_dirty = False

    def _np_values(self):
        """The live host mirror, or None when ineligible (TPU backend,
        or the 64-bit host-backed branch which IS host). Multi-process
        worlds ARE eligible since round 5 — the mirror is replicated
        per rank and every host verb reaches it as identically merged
        data (see _host_values_ok above)."""
        if self._host_backed or not self._host_values_ok:
            return None
        if self._values_np is None:
            self._values_np = np.asarray(
                self._zoo.mesh_ctx.fetch(self._values_arr)).copy()
        return self._values_np

    def _synced_values(self):
        """The jax values with pending host-mirror writes applied."""
        if self._np_dirty:
            # direct attr write: the mirror stays live (both sides fresh)
            self._values_arr = self._zoo.mesh_ctx.place(
                jnp.asarray(self._values_np), self._sharding)
            self._np_dirty = False
        return self._values_arr

    def _host_snapshot(self) -> np.ndarray:
        if self._host_backed:
            return self._values
        if self._values_np is not None:
            return self._values_np
        return self._zoo.mesh_ctx.fetch(self._values)

    # -- slot management ----------------------------------------------------

    def _rebuild_lookup(self) -> None:
        n = len(self._index)
        ks = np.fromiter(self._index.keys(), np.int64, n)
        vs = np.fromiter(self._index.values(), np.int32, n)
        order = np.argsort(ks, kind="stable")
        self._sorted_keys = ks[order]
        self._sorted_slots = vs[order]
        self._pending = {}

    def _bulk_lookup(self, keys: np.ndarray) -> np.ndarray:
        """Vectorized key -> slot (-1 = absent): searchsorted against the
        sorted cache, misses patched from the small pending dict."""
        if len(self._sorted_keys):
            pos = np.searchsorted(self._sorted_keys, keys)
            pos_c = np.minimum(pos, len(self._sorted_keys) - 1)
            hit = self._sorted_keys[pos_c] == keys
            slots = np.where(hit, self._sorted_slots[pos_c],
                             -1).astype(np.int32)
        else:
            slots = np.full(len(keys), -1, np.int32)
        if self._pending:
            pend = self._pending
            for i in np.nonzero(slots < 0)[0]:
                s = pend.get(int(keys[i]))
                if s is not None:
                    slots[i] = s
        return slots

    def _nat(self):
        """The native index, created on first index USE (not table
        construction — KvIndex.create may trigger the one-time native
        build). Nothing needs migrating at creation time: every code
        path that populates an index flows through here or Load."""
        if not self._nat_index_tried:
            self._nat_index_tried = True
            if not self._index:      # never mix: dict already has entries
                from multiverso_tpu import native as _native
                self._nat_index = _native.KvIndex.create(self.capacity)
        return self._nat_index

    def _slots_for(self, keys: np.ndarray, create: bool) -> np.ndarray:
        self._nat()
        if self._nat_index is not None:
            if create:
                slots = self._nat_index.insert(keys)
                if len(self._nat_index) >= self.capacity:
                    self._grow(len(self._nat_index))
                return slots
            return self._nat_index.lookup(keys)
        slots = self._bulk_lookup(keys)
        if create:
            miss = slots < 0
            if miss.any():
                # Vectorized slot assignment for NEW keys (round 7 —
                # the per-key python loop here was the KV push hot spot:
                # a 100k-new-key batch paid ~100k interpreter
                # iterations per Add). First-sight order is preserved
                # EXACTLY (it is what keeps multi-process index
                # replicas lockstep): sorted-unique keys are re-ranked
                # by their first occurrence in the batch, so duplicates
                # of a new key share one slot and slots issue in
                # first-appearance order, matching the old loop.
                mk = keys[miss]
                uniq, first_idx, inv = np.unique(mk, return_index=True,
                                                 return_inverse=True)
                order = np.argsort(first_idx, kind="stable")
                rank_of = np.empty(len(uniq), np.int64)
                rank_of[order] = np.arange(len(uniq))
                base = len(self._index)
                slots[miss] = (base + rank_of[inv]).astype(np.int32)
                new_keys = uniq[order].tolist()
                self._index.update(
                    zip(new_keys, range(base, base + len(new_keys))))
                self._pending.update(
                    zip(new_keys, range(base, base + len(new_keys))))
                # amortized rebuild: only once pending outgrows ~1/8 of the
                # index does the sorted cache re-sort (a key trickle never
                # pays O(N log N) per batch)
                if len(self._pending) > max(1024, len(self._index) // 8):
                    self._rebuild_lookup()
            if len(self._index) >= self.capacity:
                self._grow(len(self._index))
        return slots

    def _grow(self, needed: int) -> None:
        new_cap = self.capacity
        while new_cap <= needed:
            new_cap *= 2
        ctx = self._zoo.mesh_ctx
        new_cap = pad_to_multiple(new_cap, ctx.num_servers)
        host = np.zeros(new_cap, self.dtype)
        host[: self.capacity] = self._host_snapshot()
        self.capacity = new_cap
        if self._host_backed:
            self._values = host
            return
        if self._values_np is not None:
            # keep the host mirror authoritative; the device copy
            # rebuilds lazily on the next device-plane read
            self._values_np = host
            self._np_dirty = True
            return
        self._values = ctx.place(jnp.asarray(host), self._sharding)

    def _pad_slots(self, slots: np.ndarray,
                   bucket: Optional[int] = None) -> np.ndarray:
        CHECK(bucket is None or len(slots) <= bucket,
              f"slot batch {len(slots)} exceeds the fixed bucket {bucket}")
        b = bucket if bucket is not None else next_bucket(len(slots))
        # trash = last slot of a spare padding region: use capacity-1; it may
        # hold a live key, so padding entries carry zero delta on Add and are
        # sliced off on Get.
        out = np.full(b, self.capacity - 1, np.int32)
        out[: len(slots)] = np.where(slots < 0, self.capacity - 1, slots)
        return out

    # -- server verbs (reference kv_table.h:82-112) -------------------------

    def ProcessAdd(self, keys: np.ndarray, values: np.ndarray,
                   option: Optional[AddOption] = None) -> None:
        keys = np.asarray(keys, np.int64).ravel()
        deltas = np.asarray(values, self.dtype).ravel()
        CHECK(keys.size == deltas.size, "kv add size mismatch")
        # multihost: merge every process's (keys, values) of this
        # collective Add — concatenation order is process order, so slot
        # creation (and therefore the whole index) evolves identically on
        # all hosts (identity single-process; the windowed engine routes
        # multi-process Adds through ProcessAddParts instead)
        keys, deltas = multihost.merge_collective_add(option, keys, deltas)
        self._apply_merged_kv(keys, deltas)

    def ProcessAddParts(self, parts, my_rank: int) -> None:
        """Windowed-engine collective Add: rank-order concatenation of
        the exchanged per-rank (keys, values) — the same index evolution
        merge_collective_add produced, with no collective here.
        ``option=None`` normalizes to the default AddOption BEFORE the
        cross-rank equality CHECK (matrix _prep_add_parts parity): a
        semantically identical None-vs-default mix across ranks must
        not FatalError the world."""
        opts = self._check_parts_options(parts)
        all_keys, all_deltas = [], []
        for p in parts:
            k = np.asarray(p["keys"], np.int64).ravel()
            d = np.asarray(p["values"], self.dtype).ravel()
            CHECK(k.size == d.size, "kv add size mismatch")
            all_keys.append(k)
            all_deltas.append(d)
        self._apply_merged_kv(np.concatenate(all_keys),
                              np.concatenate(all_deltas))

    def ProcessAddRunParts(self, positions, my_rank: int) -> bool:
        """Cross-rank add-coalescing (tables/base.py contract): a
        window's collective KV Adds merge into ONE scatter-add. Always
        sound — the KV Add is plain ``+=`` with no updater (reference
        kv_table.h:82-112, option scalars never consulted), and the
        position-major/rank-major concatenation preserves the exact
        key-first-sight order sequential per-position applies would
        produce, so the slot index evolves identically on every rank.
        Declines on any validation doubt so the per-position path
        reports precise errors.

        The KV table deliberately does NOT opt into the device wire
        (device_wire_add_ok stays False): its keys must cross the host
        exchange anyway (slot creation is host control-plane logic that
        every rank replays), and the values are the same order of
        magnitude as the keys — deferring them would halve the wire
        bytes at best while buying an extra collective device program
        per position."""
        all_keys, all_deltas = [], []
        for parts in positions:
            opts = self._norm_parts_options(parts)
            if not all(o == opts[0] for o in opts):
                return False
            for p in parts:
                k = p.get("keys")
                d = p.get("values")
                if not isinstance(k, np.ndarray) \
                        or not isinstance(d, np.ndarray):
                    return False
                k = np.asarray(k, np.int64).ravel()
                d = np.asarray(d, self.dtype).ravel()
                if k.size != d.size:
                    return False
                all_keys.append(k)
                all_deltas.append(d)
        self._apply_merged_kv(np.concatenate(all_keys),
                              np.concatenate(all_deltas))
        return True

    def ProcessAddRun(self, payloads) -> bool:
        """Single-process engine add-coalescing (tables/base.py
        contract): a window's KV Adds merge into ONE scatter-add — the
        KV Add is plain ``+=`` with no updater, so merging is always
        sound, and concatenation order preserves key first-sight order.
        Implemented by REUSING the ProcessAddRunParts merged-run
        machinery with one-rank positions (round 7: the windowed engine
        previously fell back to one jit dispatch per KV Add in 1-proc
        worlds — on a remote accelerator that is one dispatch RTT per
        verb, the BENCH_r05 1.5 Melem/s wall)."""
        from multiverso_tpu.parallel import multihost
        if multihost.world_size() > 1:
            return False    # the collective window protocol owns those
        return self.ProcessAddRunParts([[p] for p in payloads], 0)

    def ProcessGetAsync(self, keys=None, option=None):
        """Two-phase Get for RTT pipelining (tables/base.py contract):
        dispatch the gather + start the device->host copy now, finalize
        later — a window of queued KV Gets overlaps its copies instead
        of paying one RTT each. Host-backed / mirror values serve
        eagerly (nothing to overlap); multi-process keeps the sync
        parts path."""
        from multiverso_tpu.parallel import multihost
        if multihost.world_size() > 1 or keys is None:
            return None
        keys = np.asarray(keys, np.int64).ravel()
        if self._host_backed or self._np_values() is not None:
            out = self.ProcessGet(keys, option)   # notes the sketch
            return lambda: out
        tsketch.note_table_access(self, keys, "kv")
        slots = self._slots_for(keys, create=False)
        padded = self._pad_slots(slots)
        vals = self._gather(self._values, jnp.asarray(padded))
        sliced = vals[: len(slots)]
        try:
            sliced.copy_to_host_async()
        except Exception:       # pragma: no cover - backend-specific
            pass
        def _finalize():
            out = np.asarray(sliced).copy()
            out[slots < 0] = 0  # absent keys read as 0
            return out
        return _finalize

    def ledger_bytes(self):
        """Accounting-ledger probe (tables/base.py contract): values
        placement + the key-index control plane. Shape math only — the
        mirror is read as the RAW attribute (``_np_values()`` would
        CREATE it with a device fetch, which a sampling thread must
        never trigger)."""
        out = {"device_bytes": 0, "host_mirror_bytes": 0, "host_bytes": 0}
        vals = self._values_arr
        if self._host_backed:
            out["host_bytes"] += int(getattr(vals, "nbytes", 0))
        else:
            out["device_bytes"] += int(getattr(vals, "nbytes", 0))
            if self._values_np is not None:
                out["host_mirror_bytes"] += int(self._values_np.nbytes)
        # control plane: the native index's ALLOCATED probing-table
        # slots (capacity >= size — the linear-probing load-factor
        # headroom is real allocation the tiering policy must see) or
        # the python sorted-array lookup
        nat = self._nat_index
        if nat is not None:
            out["host_bytes"] += 12 * nat.capacity()  # i64 key + i32 slot
        else:
            out["host_bytes"] += int(self._sorted_keys.nbytes
                                     + self._sorted_slots.nbytes)
        return out

    def mh_prepare_local_apply(self) -> None:
        """Sharded-engine pre-warm (tables/base.py contract): force the
        replicated f32 mirror live at registration (the fetch is a
        lockstep collective there). Host-backed values already ARE
        host state — nothing to warm."""
        if not self._host_backed and self._host_values_ok:
            self._np_values()

    def mh_apply_is_local(self) -> bool:
        """Pipelined-engine overlap gate (tables/base.py contract):
        host-backed (64-bit) values ARE host state, and a live
        replicated f32 mirror serves every exchanged-parts Add/Get with
        numpy — no device collectives. Rank-agreed: eligibility is
        backend config, creation happens at the first host verb's
        lockstep position, and only fenced (non-local) windows or
        device-plane callers drop it."""
        return self._host_backed or (self._host_values_ok
                                     and self._values_np is not None)

    def _apply_merged_kv(self, keys: np.ndarray, deltas: np.ndarray) -> None:
        slots = self._slots_for(keys, create=True)
        npv = self._np_values()
        if npv is not None:
            # mirror path needs no bucket padding (that exists for jit
            # shape stability only); create=True slots are all valid
            np.add.at(npv, slots, deltas)
            self._np_dirty = True
            self._note_journal_keys(keys)
            return
        padded = self._pad_slots(slots)
        pad_deltas = np.zeros(len(padded), self.dtype)
        pad_deltas[: len(slots)] = deltas
        if self._host_backed:
            self._values = self._scatter_add(self._values, padded, pad_deltas)
        else:
            self._values = self._scatter_add(self._values, jnp.asarray(padded),
                                             jnp.asarray(pad_deltas))
        self._note_journal_keys(keys)

    def _note_journal_keys(self, keys: np.ndarray) -> None:
        """Replica-plane publish journal (tables/base.py contract):
        every merged-KV apply funnels through _apply_merged_kv, so one
        mark site covers blocking, windowed and merged-run Adds. Fires
        AFTER the data update — a rejected add never dirties it."""
        journal = self._pub_journal
        if journal is not None:
            journal.mark_keys(keys)

    def ProcessGet(self, keys: np.ndarray,
                   option: Optional[GetOption] = None,
                   _union: Optional[np.ndarray] = None) -> np.ndarray:
        """``_union``: a caller that already knows every process's key
        set of this collective Get (the windowed engine's parts hooks)
        passes the precomputed union so no key collective runs here."""
        keys = np.asarray(keys, np.int64).ravel()
        # key-access skew (-mv_row_sketch): THIS rank's requested keys
        # — ProcessGetParts and the eager ProcessGetAsync branch both
        # funnel through here, so each logical Get notes once
        tsketch.note_table_access(self, keys, "kv")
        npv = self._np_values()
        if npv is not None and multihost.world_size() > 1:
            # replicated mirror: serve locally — no union round, no
            # device program (the mirror evolves in lockstep everywhere)
            slots = self._slots_for(keys, create=False)
            out = npv[np.where(slots < 0, 0, slots)]
            out[slots < 0] = 0
            return out
        union = _union
        if union is None:
            union = (multihost.union_collective_ids(keys)
                     if not self._host_backed else None)
        if union is not None:
            # collective Get over possibly different key sets: gather the
            # union with one identical device program (replicated out —
            # the fetch is local), slice ours out
            union_slots = self._slots_for(union, create=False)
            padded = self._pad_slots(union_slots)
            vals = np.asarray(self._gather_replicated(padded))
            u_out = vals[: len(union_slots)].copy()
            u_out[union_slots < 0] = 0
            return u_out[np.searchsorted(union, keys)]
        slots = self._slots_for(keys, create=False)
        npv = self._np_values()
        if npv is not None:
            out = npv[np.where(slots < 0, 0, slots)]
            out[slots < 0] = 0   # absent keys read as 0 (no padding pass)
            return out
        padded = self._pad_slots(slots)
        if self._host_backed:
            vals = self._gather(self._values, padded)
        else:
            vals = self._zoo.mesh_ctx.fetch(
                self._gather(self._values, jnp.asarray(padded)))
        out = vals[: len(slots)].copy()
        out[slots < 0] = 0  # absent keys read as default-constructed (0)
        return out

    def _gather_replicated(self, padded_slots: np.ndarray):
        """values[slots] with a REPLICATED output — every host reads the
        result locally (XLA moves the bytes over ICI; no host-collective
        reassembly)."""
        if not hasattr(self, "_gather_repl"):
            from jax.sharding import NamedSharding, PartitionSpec as P

            def _gather(values, slots):
                return values[slots]

            self._gather_repl = jax.jit(
                _gather, out_shardings=NamedSharding(
                    self._zoo.mesh_ctx.mesh, P()))
        return self._gather_repl(self._synced_values(),
                                 jnp.asarray(padded_slots))

    def ProcessGetParts(self, parts, my_rank: int):
        """One collective Get from exchanged parts: union known locally."""
        if self._host_backed or self._np_values() is not None:
            # host values / replicated mirror serve locally — skip the
            # cross-rank union entirely (ProcessGet's mirror branch
            # never reads it)
            return self.ProcessGet(**parts[my_rank])
        all_keys = [np.asarray(p["keys"], np.int64).ravel() for p in parts]
        union = np.unique(np.concatenate(all_keys))
        return self.ProcessGet(all_keys[my_rank],
                               parts[my_rank].get("option"), _union=union)

    def ProcessGetWindowParts(self, positions, my_rank: int):
        """Cross-rank get-dedup: one union gather (or the replicated
        mirror) serves every Get position of the window segment."""
        if self._host_backed:
            return None     # host-resident values: per-position is local
        npv = self._np_values()
        if npv is not None and multihost.world_size() > 1:
            out = []
            for parts in positions:
                keys = np.asarray(parts[my_rank]["keys"], np.int64).ravel()
                tsketch.note_table_access(self, keys, "kv")
                slots = self._slots_for(keys, create=False)
                vals = npv[np.where(slots < 0, 0, slots)]
                vals[slots < 0] = 0
                out.append(vals)
            return out
        pos_keys = [[np.asarray(p["keys"], np.int64).ravel() for p in parts]
                    for parts in positions]
        for rank_keys in pos_keys:
            # skew counts THIS rank's requested keys per position (the
            # union gather serves them all in one dispatch below)
            tsketch.note_table_access(self, rank_keys[my_rank], "kv")
        union = np.unique(np.concatenate(
            [k for rank_keys in pos_keys for k in rank_keys]))
        union_slots = self._slots_for(union, create=False)
        padded = self._pad_slots(union_slots)
        vals = np.asarray(self._gather_replicated(padded))
        u_out = vals[: len(union_slots)].copy()
        u_out[union_slots < 0] = 0
        return [u_out[np.searchsorted(union, rank_keys[my_rank])]
                for rank_keys in pos_keys]

    # -- device plane (matrix_table device_* counterpart) -------------------
    # A mesh-resident worker resolves its key batch ONCE on host
    # (device_slots — dynamic key sets are control-plane logic) and scans
    # the traceable gather / scatter-add over the sharded values array
    # inside its own training step, so KV rounds fuse into the caller's
    # XLA program and values never leave HBM. Bypasses the engine: no
    # single-writer arbitration — the caller owns the table while using
    # it. Multi-process, the verbs are COLLECTIVE: slot creation merges
    # every process's keys (process order, exactly ProcessAdd) so the
    # index evolves identically everywhere, and per-process slot batches
    # ride the traced round as batch-sharded global arrays
    # (device_place_slots) — scatter-add accumulates duplicates natively,
    # so no dedup pass is needed. Resolve with create=True BEFORE taking
    # device_values(): growth at resolve time replaces the backing array.

    def _check_device_plane(self) -> None:
        CHECK(not self._host_backed,
              "64-bit KV tables are host-resident (no device plane)")

    def device_slots(self, keys, create: bool = False, *,
                     bucket: Optional[int] = None) -> np.ndarray:
        """keys -> bucket-padded slot vector (pad/absent lanes -> the
        trash slot; on gather the caller masks them, on scatter their
        deltas must be zero — exactly ProcessAdd's own padding rule).
        Collective multi-process (create or not): every process's new
        keys enter the index in process order on every host, and the
        returned vectors share ONE bucket (the global max key count's
        parts_bucket) so the parts round traces identically everywhere —
        pass ``bucket`` explicitly to skip the host agreement in
        scan-style loops."""
        self._check_device_plane()
        keys = np.asarray(keys, np.int64).ravel()
        if multihost.world_size() > 1 and (create or bucket is None):
            # identical index evolution on every host: resolve the union
            # in process order first (the control plane is host logic —
            # the one host collective the KV device plane keeps); the
            # same allgather carries the per-process counts the shared
            # bucket needs. An explicit bucket with create=False is the
            # promised collective-free fast path.
            parts = multihost.host_allgather_objects_capped(keys,
                                                            "kv_slots")
            if create:
                self._slots_for(np.concatenate(parts), create=True)
            if bucket is None:
                bucket = parts_bucket(
                    max(len(p) for p in parts),
                    local_device_count(self._zoo.mesh_ctx.mesh))
        return self._pad_slots(self._slots_for(keys, create=create), bucket)

    def device_place_slots(self, padded_slots, deltas=None, *,
                           dtype=None):
        """THIS process's bucket-padded slot vector (and optional delta
        vector) -> batch-sharded global arrays for the traceable verbs.
        Collective multi-process; every process must pass the same bucket
        size (device_slots' shared-bucket agreement guarantees that).
        Device-resident deltas stay in HBM (place_parts). Single-process
        it simply places the batch on device."""
        slots = np.asarray(padded_slots, np.int32).ravel()
        nproc = multihost.world_size()
        ctx = self._zoo.mesh_ctx
        local_dev = local_device_count(ctx.mesh)
        CHECK(len(slots) % local_dev == 0,
              f"device_place_slots: bucket {len(slots)} must be a multiple "
              f"of the {local_dev} local devices (use device_slots' bucket)")
        gslots = place_parts(ctx.mesh, slots, nproc)
        if deltas is None:
            return gslots
        if isinstance(deltas, jax.Array):
            CHECK(deltas.shape == slots.shape,
                  "device_place_slots: size mismatch")
            return gslots, place_parts(ctx.mesh, deltas, nproc)
        d = np.asarray(deltas, dtype or self.dtype).ravel()
        CHECK(d.size == slots.size, "device_place_slots: size mismatch")
        return gslots, place_parts(ctx.mesh, d, nproc)

    def device_values(self) -> jax.Array:
        """The live sharded values array (hand it through your scan
        carry; write it back with device_set_values). Take it FRESH
        after any host-plane write: on the TPU path host Adds DONATE
        this buffer (a stale reference is a deleted array — loud), and
        on the CPU mirror path they land in the host mirror (a stale
        reference silently misses them and device_set_values would
        then discard them) — either way the contract is the same."""
        self._check_device_plane()
        return self._synced_values()

    def device_set_values(self, values: jax.Array) -> None:
        self._check_device_plane()
        CHECK(values.shape == (self.capacity,),
              f"values shape {values.shape} != capacity {self.capacity}")
        CHECK(values.dtype == self.dtype,
              f"values dtype {values.dtype} != table dtype {self.dtype} "
              f"(a drifted carry dtype would corrupt Store/Load and Gets)")
        self._values = values   # property setter drops the host mirror

    def device_gather_slots(self, values, padded_slots):
        """Traceable: values[slots] (mask trash lanes yourself). Accepts a
        replicated batch OR a batch-sharded parts batch
        (device_place_slots) — for parts, jit with replicated
        out_shardings and slice your process's range out of an
        addressable copy."""
        return values[padded_slots]

    def device_scatter_add_slots(self, values, padded_slots, padded_deltas):
        """Traceable: values.at[slots].add(deltas) — duplicates
        accumulate (within a batch AND across processes' parts batches);
        pad-lane deltas must be zero. Accepts replicated or parts
        batches."""
        return values.at[padded_slots].add(padded_deltas)

    @property
    def size(self) -> int:
        if self._nat_index is not None:
            return len(self._nat_index)
        return len(self._index)

    # -- serving-plane export (tables/base.py contract) ---------------------

    def serving_export(self):
        """Key-addressed copy-on-publish snapshot: (keys, values) pairs
        captured exactly like Store()'s checkpoint cut — fancy indexing
        of the host snapshot copies, so the result aliases nothing the
        live table later mutates. Absent keys keep reading as 0 (the
        live Get contract)."""
        from multiverso_tpu.serving import snapshot as ssnap
        if self._nat_index is not None:
            keys, slots = self._nat_index.items()
            slots = slots.astype(np.int64)
        else:
            keys = np.fromiter(self._index.keys(), np.int64,
                               len(self._index))
            slots = np.fromiter(self._index.values(), np.int64,
                                len(self._index))
        if len(keys):
            vals = self._host_snapshot()[slots]
        else:
            vals = np.empty(0, self.dtype)
        return ssnap.KVSnapshot(keys, vals)

    # -- checkpoint (improvement over reference kv_table.h:106-112) ---------

    def Store(self, stream) -> None:
        if self._nat_index is not None:
            keys, slots = self._nat_index.items()
            slots = slots.astype(np.int64)
        else:
            keys = np.fromiter(self._index.keys(), np.int64,
                               len(self._index))
            slots = np.fromiter(self._index.values(), np.int64,
                                len(self._index))
        if len(keys):
            vals = self._host_snapshot()[slots]
        else:
            vals = np.empty(0, self.dtype)
        stream.WriteInt(len(keys))
        stream.Write(keys.tobytes())
        stream.Write(vals.tobytes())

    def Load(self, stream) -> None:
        n = stream.ReadInt()
        keys = np.frombuffer(stream.Read(n * 8), np.int64)
        vals = np.frombuffer(stream.Read(n * self.dtype.itemsize), self.dtype)
        self._nat()
        if self._nat_index is not None:
            self._nat_index.set_items(keys,
                                      np.arange(n, dtype=np.int32))
        else:
            self._index = {int(k): i for i, k in enumerate(keys)}
            self._rebuild_lookup()
        ctx = self._zoo.mesh_ctx
        if n >= self.capacity:
            self.capacity = pad_to_multiple(max(n + 1, _MIN_BUCKET),
                                            ctx.num_servers)
        host = np.zeros(self.capacity, self.dtype)
        host[:n] = vals
        if self._host_backed:
            self._values = host
        else:
            self._values = ctx.place(jnp.asarray(host), self._sharding)


class KVWorkerTable(WorkerTable):
    """Worker half with a local cache (reference kv_table.h:19-46)."""

    telemetry_label = "kv"

    def __init__(self, dtype=np.float32):
        super().__init__()
        self.dtype = np.dtype(dtype)
        self._cache: Dict[int, float] = {}
        self._cache_buf: list = []
        self._cache_buf_elems = 0

    def Get(self, keys, option: Optional[GetOption] = None) -> np.ndarray:
        keys = np.asarray(keys, np.int64).ravel()
        vals = self.Wait(self.GetAsync({"keys": keys}, option))
        # the reference's local cache (kv_table.h:40), merged LAZILY: a
        # 100k-entry dict update per Get measured ~15ms on this host —
        # buffer the fetched arrays and merge on raw() (or past a
        # bound), keeping the contract off the Get hot path. SNAPSHOT
        # copies: the caller may reuse its key buffer or scale the
        # returned values in place before the deferred merge runs
        self._cache_buf.append((keys.copy(), vals.copy()))
        self._cache_buf_elems += len(keys)
        if self._cache_buf_elems > 2_000_000:
            self._merge_cache()
        return vals

    def _merge_cache(self) -> None:
        for k, v in self._cache_buf:
            self._cache.update(zip(k.tolist(), v.tolist()))
        self._cache_buf, self._cache_buf_elems = [], 0

    def Add(self, keys, values, option: Optional[AddOption] = None) -> None:
        keys = np.asarray(keys, np.int64).ravel()
        vals = np.asarray(values, self.dtype).ravel()
        self.Wait(self.AddAsync({"keys": keys, "values": vals}, option))

    def AddFireForget(self, keys, values,
                      option: Optional[AddOption] = None) -> None:
        """Untracked async push — no Waiter/result bookkeeping (the
        array/matrix AddFireForget contract; bursts of these coalesce
        into merged dispatches in the engine window)."""
        keys = np.asarray(keys, np.int64).ravel()
        vals = np.asarray(values, self.dtype).ravel()
        self.AddAsync({"keys": keys, "values": vals}, option, track=False)

    # -- write combining (round 7; tables/base.py contract) -----------------

    def _combinable_fire_forget(self, payload) -> bool:
        """KV pushes always combine: the server Add is plain ``+=``
        with no updater, and concatenation preserves key first-sight
        order (what keeps multi-process index replicas lockstep)."""
        return (isinstance(payload.get("keys"), np.ndarray)
                and isinstance(payload.get("values"), np.ndarray))

    def _combine_fire_forget(self, payloads) -> dict:
        return {"keys": np.concatenate([p["keys"] for p in payloads]),
                "values": np.concatenate([p["values"] for p in payloads])}

    def raw(self) -> Dict[int, float]:
        """Local cache of last-fetched values (reference kv_table.h:40)."""
        self._merge_cache()
        return self._cache

    def server(self) -> KVServerTable:
        """The co-located server half — device-plane access (same contract
        as MatrixWorkerTable.server())."""
        return self._zoo.server_tables[self.table_id]
