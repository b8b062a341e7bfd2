"""ArrayTable — 1-D dense vector, contiguous-range sharded over servers.

Behavioral equivalent of reference include/multiverso/table/array_table.h +
src/table/array_table.cpp: ``Get``/``Add`` always move the whole table
(key = -1 semantics, array_table.cpp:29-67); the store is split into
contiguous per-server ranges with the last server taking the remainder
(array_table.cpp:101-105); the server applies the configured updater
(array_table.cpp:116-143); ``Store/Load`` checkpoint the shard
(array_table.cpp:145-154).

TPU design: the whole table is ONE jax array sharded along the mesh
``server`` axis (padded to a multiple of num_servers so shard_map-style
layouts stay legal). ``Add`` = host->HBM transfer of the delta + a jit'd,
donated elementwise updater on the sharded store — XLA keeps each shard's
update local to its device, which is exactly the reference's
per-server-shard Add without any message serialization. ``Get`` = a
device->host gather of the sharded array (XLA all-gathers over ICI).

Unlike the reference, tiny tables (size < num_servers) are supported —
padding absorbs them (the reference CHECKs against this,
array_table.cpp:14, and its Python binding skips a test because of it,
binding test_multiverso.py:36-41).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu.parallel import multihost
from multiverso_tpu.parallel.mesh import pad_to_multiple, partition_offsets
from multiverso_tpu.tables.base import ServerTable, TableOption, WorkerTable
from multiverso_tpu.updaters.base import (AddOption, CreateUpdater, GetOption,
                                          Updater, stack_workers,
                                          unstack_workers)
from multiverso_tpu.utils.log import CHECK


@dataclass
class ArrayTableOption(TableOption):
    """reference multiverso.h ArrayTableOption equivalent."""

    size: int = 0
    updater_type: Optional[str] = None  # None -> updater_type flag

    def make_server(self, zoo):
        return ArrayServer(self.size, self.dtype, zoo, self.updater_type)

    def make_worker(self, zoo):
        return ArrayWorker(self.size, self.dtype)


class ArrayServer(ServerTable):
    def __init__(self, size: int, dtype, zoo, updater_type: Optional[str] = None):
        CHECK(size > 0, "ArrayTable size must be positive")
        self.size = size
        self.dtype = np.dtype(dtype)
        self._zoo = zoo
        ctx = zoo.mesh_ctx
        self.num_servers = ctx.num_servers
        self.padded = pad_to_multiple(size, self.num_servers)
        self.updater = CreateUpdater(updater_type)

        self._sharding = ctx.sharding_1d()
        data = jnp.zeros((self.padded,), self.dtype)
        aux = self.updater.init_aux((self.padded,), self.dtype, zoo.num_workers)
        self.state = {
            "data": ctx.place(data, self._sharding),
            # every state leaf is 1-D on the data's axis (per-worker state:
            # updaters.base.worker_rows)
            "aux": jax.tree.map(lambda a: ctx.place(a, self._sharding), aux),
        }

        # the engine's jitted programs ARE the device-plane bodies —
        # one source of truth for the updater call convention
        self._update = jax.jit(self.device_update, donate_argnums=(0,))
        self._update_parts_jit = jax.jit(self.device_update_parts,
                                         donate_argnums=(0,))
        self._access = jax.jit(self.device_access)
        self._has_access = type(self.updater).access is not Updater.access
        # engine add-run merging (ProcessAddRunParts) is sound for
        # exactly the LINEAR aux-free updaters — pre-summing a window of
        # whole-table deltas equals sequential application then (the
        # matrix table's _merge_adds gate; updaters/base.py combine_scale)
        self._merge_adds = (self.updater.combine_scale is not None
                            and not jax.tree.leaves(aux))

    def ProcessAdd(self, values: np.ndarray, option: AddOption) -> None:
        values = np.asarray(values, self.dtype).ravel()
        CHECK(values.size == self.size, "Add size mismatch")
        # multihost: one logical Add is issued collectively by every
        # process; summing the per-process deltas first gives the reference
        # semantics (every worker's Add accumulates, src/server.cpp:48-58)
        # — identity in a single-process job. (The windowed engine routes
        # multi-process Adds through ProcessAddParts instead — this
        # collective remains for the BSP engine and direct callers.)
        values = multihost.sum_collective_add(option, values)
        self._apply_summed(values, option)

    def _apply_summed(self, values: np.ndarray, option: AddOption) -> None:
        if self.padded != self.size:
            values = np.pad(values, (0, self.padded - self.size))
        delta = self._zoo.mesh_ctx.place(values, self._sharding)
        self.state = self._update(self.state, delta, option.as_jnp())
        self._note_journal_all()

    def _note_journal_all(self) -> None:
        """Replica-plane publish journal (tables/base.py contract):
        every array Add is whole-vector, so the journal is a flag —
        the fan-out delta ships the full values when anything moved.
        Fires AFTER the data update, from every apply site (host sums
        and both device-wire paths)."""
        journal = self._pub_journal
        if journal is not None:
            journal.mark_all()

    def ProcessAddParts(self, parts, my_rank: int) -> None:
        """Windowed-engine collective Add: every rank's payload arrived
        through the one window exchange — sum them here with NO further
        host collective (multihost.py sum_collective_add semantics).
        ``option=None`` normalizes to the default AddOption BEFORE the
        cross-rank equality CHECK (matrix _prep_add_parts parity): a
        semantically identical None-vs-default mix across ranks must
        not FatalError the world."""
        opts = self._check_parts_options(parts)
        vals = []
        for p in parts:
            v = np.asarray(p["values"], self.dtype).ravel()
            CHECK(v.size == self.size, "Add size mismatch")
            vals.append(v)
        summed = np.sum(vals, axis=0).astype(self.dtype)
        self._apply_summed(summed, opts[my_rank])

    def ProcessAddRunParts(self, positions, my_rank: int) -> bool:
        """Cross-rank add-coalescing (tables/base.py contract): a
        window's whole-table collective Adds pre-sum into ONE apply —
        sound exactly for linear aux-free updaters (option scalars are
        ignored by contract then, so per-position options may differ).
        Declines on any validation doubt so the per-position path
        reports precise errors."""
        if not self._merge_adds:
            return False
        vals = []
        for parts in positions:
            opts = self._norm_parts_options(parts)
            if not all(o == opts[0] for o in opts):
                return False
            for p in parts:
                v = p.get("values")
                if not isinstance(v, np.ndarray) or v.size != self.size:
                    return False
                vals.append(np.asarray(v, self.dtype).ravel())
        summed = np.sum(vals, axis=0).astype(self.dtype)
        self._apply_summed(summed, AddOption())
        return True

    # -- DEVICE-wire transport (round 6; tables/base.py contract) -----------

    def device_wire_add_ok(self, payload) -> bool:
        """A whole-table dense delta can ride the device wire: the
        per-rank deltas stack batch-sharded (device_place_parts_delta)
        and sum inside ONE traced collective round
        (device_update_parts) — no host staging of the values."""
        v = payload.get("values")
        return isinstance(v, np.ndarray) and v.size == self.size

    def ProcessAddPartsDevice(self, parts, my_rank: int) -> None:
        """One collective whole-table Add whose values ride the device
        wire (deferred values are wire.DeferredArray placeholders; ours
        carries the real array in .local)."""
        from multiverso_tpu.parallel import wire
        opts = self._check_parts_options(parts)
        for p in parts:
            v = p["values"]
            size = v.size if isinstance(v, wire.DeferredArray) \
                else np.asarray(v).size
            CHECK(size == self.size, "Add size mismatch")
        mine = parts[my_rank]["values"]
        local = mine.local if isinstance(mine, wire.DeferredArray) else mine
        CHECK(local is not None,
              "device-wire Add lost its local values (engine bug)")
        gdelta = self.device_place_parts_delta(
            np.asarray(local, self.dtype).ravel())
        self.state = self._update_parts_jit(self.state, gdelta,
                                            opts[0].as_jnp())
        self._note_journal_all()

    def ProcessAddRunPartsDevice(self, positions, my_rank: int) -> bool:
        """Merged DEVICE-wire run (tables/base.py contract): a window's
        deferred whole-table Adds pre-sum THIS rank's local deltas and
        apply in ONE parts round — sound exactly for linear aux-free
        updaters (the ProcessAddRunParts contract). Accept/decline is
        computed from the EXCHANGED metadata, identically on every
        rank."""
        if not self._merge_adds:
            return False
        from multiverso_tpu.parallel import wire
        my_vals = []
        for parts in positions:
            opts = self._norm_parts_options(parts)
            if not all(o == opts[0] for o in opts):
                return False
            for r, p in enumerate(parts):
                v = p.get("values")
                if isinstance(v, wire.DeferredArray):
                    size = v.size
                elif isinstance(v, np.ndarray):
                    size = v.size
                else:
                    return False
                if size != self.size:
                    return False
                if r == my_rank:
                    local = v.local if isinstance(v, wire.DeferredArray) \
                        else v
                    CHECK(local is not None,
                          "device-wire Add lost its local values "
                          "(engine bug)")
                    my_vals.append(np.asarray(local, self.dtype).ravel())
        summed = np.sum(my_vals, axis=0).astype(self.dtype)
        gdelta = self.device_place_parts_delta(summed)
        self.state = self._update_parts_jit(self.state, gdelta,
                                            AddOption().as_jnp())
        self._note_journal_all()
        return True

    def ProcessGet(self, option: GetOption) -> np.ndarray:
        if multihost.world_size() > 1:
            # replicate through XLA (ICI) so every rank reads the full
            # table locally — no host-collective reassembly round
            return self._replicated_full()[: self.size].copy()
        out = self._access(self.state, None)
        return self._zoo.mesh_ctx.fetch(out)[: self.size]

    def _replicated_full(self) -> np.ndarray:
        if not hasattr(self, "_access_repl"):
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._access_repl = jax.jit(
                self.device_access,
                out_shardings=NamedSharding(self._zoo.mesh_ctx.mesh, P()))
        return np.asarray(self._access_repl(self.state, None))

    def ProcessGetWindowParts(self, positions, my_rank: int):
        """Every array Get is the whole table: one replicated read serves
        the whole window segment (cross-rank get-dedup)."""
        full = self._replicated_full()[: self.size]
        return [full.copy() for _ in positions]

    def ProcessGetAsync(self, option: GetOption = None):
        if multihost.world_size() > 1:
            return None  # multihost fetch is a collective — keep sync path
        out = self._access(self.state, None)
        if not self._has_access:
            # identity access: XLA may alias the jit output to the live
            # state buffer; an Add drained later in the same pipeline
            # window donates that buffer (donate_argnums) and the pending
            # finalize would read a deleted array. Snapshot first — same
            # guard as MatrixServerTable.ProcessGetAsync.
            out = jnp.copy(out)
        out.copy_to_host_async()
        return lambda: np.asarray(out)[: self.size]

    def raw(self) -> jax.Array:
        """The live sharded device array (padded)."""
        return self.state["data"]

    # -- device plane (matrix/kv device_* counterpart) ----------------------
    # Traceable whole-table verbs for mesh-resident workers: scan them over
    # the state dict in your own step (PS rounds fuse into one XLA
    # program). One device-plane writer per round; multi-process the
    # rounds are COLLECTIVE — every process traces the identical program
    # over the globally-sharded state, passing either an identical
    # replicated delta (one logical writer) or its OWN delta through
    # device_place_parts_delta + device_update_parts (per-process deltas
    # summed inside the traced round, the reference's every-worker's-Add-
    # accumulates semantics).

    def device_state(self):
        """The live {'data','aux'} pytree (scan carry; write back with
        device_set_state). Host-plane Adds donate these buffers — re-take
        after any interleaved engine Add."""
        return self.state

    def device_set_state(self, state) -> None:
        CHECK(state["data"].shape == (self.padded,)
              and state["data"].dtype == self.dtype,
              "device_set_state: data leaf shape/dtype mismatch")
        # the aux carry must not drift either (structure + leaf
        # shape/dtype): drifted aux would corrupt the next host-plane
        # update's trace and the checkpoint's serialized state
        old_aux = self.state["aux"]
        CHECK(jax.tree.structure(state["aux"])
              == jax.tree.structure(old_aux),
              "device_set_state: aux tree structure drifted")
        for new_leaf, old_leaf in zip(jax.tree.leaves(state["aux"]),
                                      jax.tree.leaves(old_aux)):
            CHECK(new_leaf.shape == old_leaf.shape
                  and new_leaf.dtype == old_leaf.dtype,
                  f"device_set_state: aux leaf drifted "
                  f"({old_leaf.shape}/{old_leaf.dtype} -> "
                  f"{new_leaf.shape}/{new_leaf.dtype})")
        self.state = state

    def device_update(self, state, padded_delta, opt):
        """Traceable: one whole-table Add through the table's updater
        (delta must be padded to ``self.padded``; opt = AddOption.as_jnp())."""
        new_data, new_aux = self.updater.update_worker(
            state["data"], state["aux"], padded_delta, opt,
            self._zoo.num_workers, self.num_servers)
        return {"data": new_data, "aux": new_aux}

    def device_access(self, state, opt=None):
        """Traceable: the whole table through the updater's access hook
        (slice [: size] yourself if you need the logical view)."""
        return self.updater.access(state["data"], state["aux"], opt)

    def device_place_parts_delta(self, local_delta) -> jax.Array:
        """THIS process's whole-table delta (logical ``size`` or padded
        length) -> a ``(nproc * padded,)`` global array whose per-process
        slice is that process's delta, for device_update_parts.
        Collective multi-process; device-resident deltas stay in HBM
        (place_parts). ``padded`` is a multiple of num_servers, so the
        global stack always shards evenly."""
        from multiverso_tpu.parallel.mesh import place_parts
        if isinstance(local_delta, jax.Array):
            d = local_delta.ravel().astype(self.dtype)
            if d.shape[0] == self.size and self.padded != self.size:
                d = jnp.pad(d, (0, self.padded - d.shape[0]))
        else:
            d = np.asarray(local_delta, self.dtype).ravel()
            if d.size == self.size and self.padded != self.size:
                d = np.pad(d, (0, self.padded - d.size))
        CHECK(d.shape[0] == self.padded, "parts delta size mismatch")
        return place_parts(self._zoo.mesh_ctx.mesh, d,
                           multihost.world_size())

    def device_update_parts(self, state, parts_delta, opt):
        """Traceable: one collective whole-table Add from per-process
        deltas — ``parts_delta`` is the stacked global array from
        device_place_parts_delta; the per-process contributions sum
        inside the traced round (XLA inserts the collectives), then the
        table's updater applies the merged delta exactly once."""
        nproc = parts_delta.shape[0] // self.padded
        delta = parts_delta.reshape(nproc, self.padded).sum(axis=0)
        return self.device_update(state, delta, opt)

    # -- serving-plane export (tables/base.py contract) ---------------------

    def serving_export(self):
        """Whole-vector copy-on-publish snapshot. Arrays are the small
        whole-table family — device residence would buy nothing over
        one fetch, and ProcessGet already IS the training view (access()
        applied, replicated read in multi-process worlds, which is a
        matched collective inside the Publish barrier dispatch)."""
        from multiverso_tpu.serving import snapshot as ssnap
        return ssnap.VectorSnapshot(
            np.asarray(self.ProcessGet(GetOption())))

    # -- checkpoint (reference array_table.cpp:145-154) ---------------------

    def Store(self, stream) -> None:
        stream.WriteInt(self.size)
        data = self._zoo.mesh_ctx.fetch(self.state["data"])[: self.size]
        stream.Write(data.tobytes())

    def Load(self, stream) -> None:
        size = stream.ReadInt()
        CHECK(size == self.size, "checkpoint size mismatch")
        raw = stream.Read(size * self.dtype.itemsize)
        values = np.frombuffer(raw, self.dtype).copy()
        if self.padded != self.size:
            values = np.pad(values, (0, self.padded - self.size))
        ctx = self._zoo.mesh_ctx
        self.state = dict(self.state)
        self.state["data"] = ctx.place(jnp.asarray(values), self._sharding)

    # -- aux (updater state) <-> logical layout, for the checkpoint driver --

    # Logical form: shared state (size,), per-worker state (workers, size),
    # whatever the mesh and however a shard stacks its workers.

    def aux_to_logical(self, keypath: str, leaf) -> np.ndarray:
        """A stored state leaf -> its logical form (padding stripped)."""
        host = self._zoo.mesh_ctx.fetch(leaf)
        if self.updater.is_per_worker(keypath):
            host = unstack_workers(host, self._zoo.num_workers,
                                   self.num_servers)
        return host[..., : self.size]

    def aux_from_logical(self, keypath: str, arr: np.ndarray) -> np.ndarray:
        pad = self.padded - self.size
        if pad:
            widths = [(0, 0)] * (arr.ndim - 1) + [(0, pad)]
            arr = np.pad(arr, widths)
        if self.updater.is_per_worker(keypath):
            arr = stack_workers(arr, self.num_servers)
        return arr


class ArrayWorker(WorkerTable):
    """Worker half (reference array_table.h:13-39)."""

    telemetry_label = "array"

    def __init__(self, size: int, dtype=np.float32):
        super().__init__()
        self.size = size
        self.dtype = np.dtype(dtype)

    # sync verbs (reference array_table.cpp:29-47)
    def Get(self, buffer: Optional[np.ndarray] = None,
            option: Optional[GetOption] = None) -> np.ndarray:
        result = self.Wait(self.GetAsync({}, option))
        if buffer is not None:
            np.copyto(buffer, result)
            return buffer
        return result

    def Add(self, delta: np.ndarray, option: Optional[AddOption] = None) -> None:
        self.Wait(self.AddAsync({"values": np.asarray(delta, self.dtype)}, option))

    # async verbs returning msg ids (reference table.cpp:41-82)
    def GetAsyncHandle(self, option: Optional[GetOption] = None) -> int:
        return self.GetAsync({}, option)

    def AddAsyncHandle(self, delta: np.ndarray,
                       option: Optional[AddOption] = None) -> int:
        return self.AddAsync({"values": np.asarray(delta, self.dtype)}, option)

    def AddFireForget(self, delta: np.ndarray,
                      option: Optional[AddOption] = None) -> None:
        """Untracked async push — no Waiter/result bookkeeping (used by
        training loops that push every minibatch and never wait)."""
        self.AddAsync({"values": np.asarray(delta, self.dtype)}, option,
                      track=False)

    def server(self) -> ArrayServer:
        """The co-located server half — device-plane access (same
        contract as MatrixWorkerTable.server())."""
        return self._zoo.server_tables[self.table_id]

    def Partition(self, num_servers: Optional[int] = None) -> List[Tuple[int, int]]:
        """Pure sharding math, unit-testable without a server
        (reference Test/unittests/test_array.cpp:47-66 pattern)."""
        if num_servers is None:
            num_servers = self._zoo.num_servers
        return partition_offsets(self.size, num_servers)
