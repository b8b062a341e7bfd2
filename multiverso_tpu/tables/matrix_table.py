"""MatrixTable — 2-D dense matrix, row-sharded over servers.

Behavioral equivalent of reference include/multiverso/table/matrix_table.h +
src/table/matrix_table.cpp (and the merged "matrix v2" src/table/matrix.cpp):
whole-table or row-set ``Get``/``Add``; the reference maps rows to servers
by ``row / (num_rows / num_servers)`` with the tail on the last server
(matrix_table.cpp:24-46) — here ownership uses ceil-sized equal blocks
instead (jax shards must be uniform; see parallel/mesh.py
``storage_partition_server``); the server applies the updater per row
(matrix_table.cpp:387-418); optional random row initialization
(matrix_table.cpp:372-384); ``Store/Load`` checkpointing
(matrix_table.cpp:457-465).

TPU design: storage is ONE jax array sharded on the row axis over the mesh
``server`` axis, in an *interleaved* layout — each server shard holds
``block_rows`` contiguous logical rows plus one **trash row** at its tail.
Row-set ops run under ``shard_map``: every shard maps the (replicated)
global id vector to local ids, routes out-of-shard and padding lanes to its
trash row, and gathers/scatters only the requested rows — the Pallas
kernels in multiverso_tpu/ops do one row-DMA per id on TPU, and the
assembled Get result is a ``psum`` of masked shard contributions, so only
the requested rows ever ride ICI (no full-table all-gather, mirroring the
reference where only the partitioned row payloads cross the network,
matrix_table.cpp:235-296). Row-id batches are padded to power-of-two
buckets (pad lane = -1) so XLA compiles a handful of shapes. Updater
state is row-shaped storage sharded along the same row axis and read and
written alongside the data rows, on their path; per-worker state (AdaGrad)
stacks a shard's workers block after block, and an Add touches the rows
of the worker that sent it (``_aux_lanes``). Duplicate ids inside one Add
are pre-combined on the host (np.add.at) because scatter order is
undefined — the reference applies rows sequentially so duplicates stack;
combining first preserves the default/sgd semantics and is the documented
contract for the others.
"""

from __future__ import annotations

import functools
import threading
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from multiverso_tpu import ops
from multiverso_tpu.parallel import multihost, wire
from multiverso_tpu.parallel.mesh import (SERVER_AXIS, ceil_block_rows,
                                          local_device_count, next_bucket,
                                          parts_bucket, place_parts,
                                          storage_partition_server)
from multiverso_tpu.tables import crossing
from multiverso_tpu.tables.base import ServerTable, TableOption, WorkerTable
from multiverso_tpu.telemetry import metrics as tmetrics
from multiverso_tpu.telemetry import sketch as tsketch
from multiverso_tpu.telemetry import startup
from multiverso_tpu.telemetry import trace as ttrace
from multiverso_tpu.updaters.base import (AddOption, CreateUpdater, GetOption,
                                          stack_workers, unstack_workers)
from multiverso_tpu.utils.log import CHECK, Log


@functools.partial(jax.jit, static_argnames=("bucket",))
def _pad_row_batch(deltas: jax.Array, bucket: int):
    """Pad an exact-size delta batch to its bucket ON DEVICE (pad delta =
    0). The host sends exact-size DELTAS — host->device wire bytes are
    what the protocol pays for (the reference likewise ships only the
    partitioned row payloads, matrix_table.cpp:235-296), and a delta row is
    512 B to 8 KB — and this tiny jitted pad (one compile per distinct
    batch size) expands to the handful of shapes the big row program is
    compiled for. Ids are 4 bytes a row: they are padded on the host
    (``_device_ids``) and copied once. Call through ``_pad_rows``, which
    runs no program when there is nothing to pad."""
    pad = bucket - deltas.shape[0]
    return jnp.concatenate(
        [deltas, jnp.zeros((pad, deltas.shape[1]), deltas.dtype)])


def _pad_rows(deltas: jax.Array, bucket: int) -> jax.Array:
    """``deltas`` at ``bucket`` rows: the array itself when the batch is
    the bucket (a pad of nothing is still a dispatch, and a copy of its
    operand — 1.34 GB for a whole-table delta), else ``_pad_row_batch``.
    The row programs donate the state alone, so the caller's array
    survives either way."""
    if deltas.shape[0] == bucket:
        return deltas
    with crossing.call("_pad_row_batch"):
        return _pad_row_batch(deltas, bucket)


def _cut_rows(rows: jax.Array, n: int) -> jax.Array:
    """The first ``n`` of a gather's bucket of rows, ON THE DEVICE: the
    bucket itself when it is ``n`` long (no program, as ``_pad_rows``),
    else a slice program, a launch of its own and a program a distinct
    ``n``. For rows that stay in HBM (``device_fetch_rows``: exactly
    ``len(row_ids)`` of them); a host-bound bucket is cut to one of its
    eighths at most (``_leaving_rows``)."""
    if n == rows.shape[0]:
        return rows
    with crossing.call("slice"):
        return rows[:n]


#: The most pad bytes a host-bound bucket carries across the boundary
#: for the host to drop (``_leaving_rows``): half of where the two costs
#: meet on a v5e. The slice program's launch costs the host 0.70 ms alone
#: and 0.9 ms on the engine's thread behind four workers (the gather's,
#: jitted, 0.18 to 0.22 ms); pad bytes come back at 0.11 ms a MB. 10,000
#: rows x 50 f32 gathered, copied back and cut, by the pad carried
#: (PR 38's chip run, medians of 40): 0.05 MB 0.78 ms against 1.77 ms
#: with the slice program, 2 MB 1.03 / 1.82, 4 MB 1.26 / 1.77, 8 MB
#: 1.66 / 1.78. A quarter-octave rung leaves a pad under a quarter of the
#: rows asked for, so only a Get of more than 16 MB reaches this. The
#: same bytes decide how a host delta crosses (``_place_rows``).
_HOST_CUT_PAD_BYTES = 4 << 20


def _leaving_rows(rows: jax.Array, n: int) -> jax.Array:
    """What of a gather's bucket is copied back when its first ``n`` rows
    are wanted ON THE HOST; the caller takes ``[:n]`` of the host array,
    a view, whichever this returns. The pad (``bucket - n`` rows of the
    trash row) is carried back and cut by that view while it is under
    ``_HOST_CUT_PAD_BYTES`` (no slice program, one launch a Get and not
    two) and, whatever its bytes, while it is at most a quarter of the
    rows asked for, which the bucket ladder's rungs over 256 rows keep
    it: a block of the WordEmbedding app asks for a million rows, a
    different count every block, and a program cut to that count would
    be compiled while the worker waits. A larger pad over the constant
    (a short bucket of very wide rows; a caller's own bucket) is cut on
    the device to the shortest of the bucket's EIGHTHS that holds the
    rows: at most eight programs a bucket, never one a row count. One
    step of ``table.get.host_cuts`` or ``table.get.device_cuts`` a
    bucket longer than ``n`` (both registered at 0 by the first)."""
    bucket = rows.shape[0]
    if n == bucket:
        return rows
    host_cuts = tmetrics.counter("table.get.host_cuts")
    device_cuts = tmetrics.counter("table.get.device_cuts")
    pad = bucket - n
    if (pad * (rows.nbytes // bucket) <= _HOST_CUT_PAD_BYTES
            or 4 * pad <= n):
        host_cuts.inc()
        return rows
    device_cuts.inc()
    eighth = -(-bucket // 8)
    return _cut_rows(rows, min(bucket, -(-n // eighth) * eighth))


@functools.partial(jax.jit, static_argnames=("bucket",))
def _join_row_pieces(pieces, last_at, bucket: int):
    """``_pad_row_batch``'s result from a batch that crossed in pieces of
    one shape (``_place_rows``): every piece but the last laid end to
    end, zeros up to ``bucket``, and the last piece, which ENDS at the
    batch's last row and so overlaps the one before it, written at row
    ``last_at`` (a traced scalar). One program a piece count, whatever
    the row count."""
    body, last = pieces[:-1], pieces[-1]
    rest = bucket - len(body) * last.shape[0]
    joined = jnp.concatenate(
        [*body, jnp.zeros((rest, last.shape[1]), last.dtype)])
    return lax.dynamic_update_slice(joined, last, (last_at, 0))


def _place_rows(deltas: np.ndarray, bucket: int) -> jax.Array:
    """A host delta batch of a row Add on the device, at ``bucket`` rows
    (pad delta = 0). While the pad is under ``_HOST_CUT_PAD_BYTES`` the
    batch crosses exact-size and ``_pad_row_batch`` pads it there: fewer
    bytes over the boundary, one program a distinct batch size, and the
    sizes of small verbs repeat. Over it (a batch of more than 16 MB: a
    block of the WordEmbedding app sends a million rows, a different
    count every block, and a pad program compiled for each is the
    sender's wait) it crosses in pieces of an eighth of the bucket,
    views of the sender's array: no copy and no zeroing on the host. The
    last piece is the batch's last eighth-of-a-bucket rows, so every
    piece has one shape, and ``_join_row_pieces`` is one program a piece
    count. ``table.add.host_pieces`` counts the pieces crossed."""
    n, piece = deltas.shape[0], bucket // 8
    pieces = tmetrics.counter("table.add.host_pieces")
    if ((bucket - n) * deltas[:1].nbytes <= _HOST_CUT_PAD_BYTES
            or bucket % 8 or n < piece):
        return _pad_rows(crossing.place(deltas), bucket)
    body = [deltas[at: at + piece] for at in range(0, n - piece, piece)]
    placed = crossing.place([*body, deltas[n - piece:]], jax.device_put)
    pieces.inc(len(placed))
    with crossing.call("_join_row_pieces"):
        return _join_row_pieces(placed, np.int32(n - piece), bucket=bucket)


def _combine_duplicate_rows(ids: np.ndarray, deltas: np.ndarray,
                            num_cols: int, dtype):
    """Host pre-combine of duplicate row ids by SUM (scatter order on
    duplicates is undefined — module docstring). One np.unique pass
    serves both the dup check and the inverse mapping."""
    ids = np.asarray(ids, np.int32).ravel()
    deltas = np.asarray(deltas, dtype).reshape(len(ids), num_cols)
    uniq, inverse = np.unique(ids, return_inverse=True)
    if len(uniq) == len(ids):
        return ids, deltas
    combined = np.zeros((len(uniq), num_cols), dtype)
    # np.add.at is a scalar loop (~20x slower than slice assignment) and
    # was the merged-Add hot spot: restrict it to the (typically few)
    # positions whose row actually duplicates; singletons assign directly
    counts = np.bincount(inverse, minlength=len(uniq))
    dup_pos = counts[inverse] > 1
    combined[inverse[~dup_pos]] = deltas[~dup_pos]
    np.add.at(combined, inverse[dup_pos], deltas[dup_pos])
    return uniq.astype(np.int32), combined


# -- in-trace accumulators for the multi-process compressed window path ------
# Each reconstructs ONE rank's delta block ON DEVICE and adds it into the
# union-indexed combined batch (``inv`` maps block rows to union rows; pad
# lanes carry an out-of-range index — scatter drops them). Ranks apply in
# rank order, so cross-rank duplicate rows sum in exactly the pairwise
# order the host merge (np.add.at over the rank-concatenated batch) uses —
# the sparse (exact) wire therefore stays BIT-IDENTICAL to the
# uncompressed path.

@functools.partial(jax.jit, donate_argnums=(0,))
def _acc_dense_part(combined, inv, block):
    return combined.at[inv].add(block)


@functools.partial(jax.jit, static_argnames=("rows", "cols"),
                   donate_argnums=(0,))
def _acc_sparse_part(combined, inv, idx, val, *, rows, cols):
    block = jnp.zeros((rows * cols,), combined.dtype).at[idx].set(
        val.astype(combined.dtype))
    return combined.at[inv].add(block.reshape(rows, cols))


@functools.partial(jax.jit, static_argnames=("rows", "cols"),
                   donate_argnums=(0,))
def _acc_1bit_part(combined, inv, packed, pos, neg, *, rows, cols):
    shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
    bits = ((packed[:, None] >> shifts) & 1).astype(jnp.bool_)
    lanes = bits.reshape(-1)[: rows * cols].reshape(rows, cols)
    block = jnp.where(lanes, pos[:, None], neg[:, None]).astype(
        combined.dtype)
    return combined.at[inv].add(block)


@dataclass
class MatrixTableOption(TableOption):
    num_rows: int = 0
    num_cols: int = 0
    _supports_compress = True
    updater_type: Optional[str] = None
    initializer: Optional[Callable[[Tuple[int, int]], np.ndarray]] = None

    def make_server(self, zoo):
        return MatrixServerTable(self.num_rows, self.num_cols, self.dtype, zoo,
                                 self.updater_type, self.initializer,
                                 compress=self.compress)

    def make_worker(self, zoo):
        return MatrixWorkerTable(self.num_rows, self.num_cols, self.dtype,
                                 compress=self.compress)


class MatrixServerTable(ServerTable):
    #: replica-plane journal granularity (tables/base.py contract):
    #: row-addressed — the fan-out delta ships dirtied rows
    publish_journal_kind = "rows"

    def __init__(self, num_rows: int, num_cols: int, dtype, zoo,
                 updater_type: Optional[str] = None,
                 initializer: Optional[Callable] = None,
                 compress: Optional[str] = None):
        CHECK(num_rows > 0 and num_cols > 0, "matrix dims must be positive")
        CHECK(compress in (None, "sparse", "1bit"),
              f"unknown compress mode {compress!r}")
        self.compress = compress
        #: wire accounting for compressed Adds: what the payload would
        #: have cost dense vs what actually crossed host->device
        #: (mirrored into the telemetry counters
        #: wire.compress.{dense,payload}_bytes via _note_wire)
        self.wire_stats = {"dense_bytes": 0, "payload_bytes": 0}
        self.num_rows = num_rows
        self.num_cols = num_cols
        self.dtype = np.dtype(dtype)
        self._zoo = zoo
        ctx = zoo.mesh_ctx
        self.num_servers = ctx.num_servers
        # Interleaved storage: each shard = block_rows logical rows + 1 trash.
        self.block_rows = ceil_block_rows(num_rows, self.num_servers)
        self.shard_rows = self.block_rows + 1
        self.padded_rows = self.num_servers * self.shard_rows
        # Columns padded to the 128-lane tile (ops.padded_cols): aligned row
        # slices are what the hot path needs; padded cols hold zeros forever
        # (every updater is identity on a zero delta).
        self.store_cols = ops.padded_cols(num_cols, self.dtype.itemsize)
        if jax.default_backend() == "tpu" and not ops.use_pallas(
                jax.ShapeDtypeStruct((self.shard_rows, self.store_cols),
                                     self.dtype)):
            Log.Info("matrix table %dx%d %s (%d stored columns): row "
                     "writes take the XLA scatter, not the Pallas row "
                     "kernels", num_rows, num_cols, self.dtype.name,
                     self.store_cols)
        self.updater = CreateUpdater(updater_type)
        self._mesh = ctx.mesh

        self._sharding = ctx.sharding_rows()
        # what the row programs declare for ids and option scalars (P()
        # over the table's mesh): small operands are placed so, and the
        # program reshards nothing on entry. Only a process that owns the
        # whole mesh can place a replicated array by itself; otherwise
        # they stay process-local, which jit takes as replicated.
        self._replicated = (ctx.replicated()
                            if local_device_count(ctx.mesh) == ctx.mesh.size
                            else None)
        self._opt_cache: Dict[tuple, Dict[str, jax.Array]] = {}
        self._opt_lock = threading.Lock()
        # round 11 — access-skew measurement (-mv_row_sketch): a
        # bounded Space-Saving top-K over Get row ids, created lazily
        # when the flag arms (telemetry/sketch.py; the off path is one
        # cached int read per Get). The groundwork for the ROADMAP's
        # giant-table hot-row cache: /metrics carries the top-share
        # gauge, the Dashboard [RowSkew] line + /perf carry the rows.
        self._row_sketch = None
        self._row_sketch_notes = 0
        # table.create_s: the initialiser, _to_storage and placement below
        # (which materialises the whole table on one device first)
        with startup.phase("table.create", histogram=True):
            storage = (self.padded_rows, self.store_cols)
            if initializer is not None:
                init = np.asarray(initializer((num_rows, num_cols)),
                                  self.dtype)
                data = self._to_storage(init)  # host numpy; place() shards it
            else:
                data = jnp.zeros(storage, self.dtype)
            aux = self.updater.init_aux(storage, self.dtype, zoo.num_workers)
            self.state = {
                "data": ctx.place(data, self._sharding),
                "aux": jax.tree.map(
                    lambda a: ctx.place(a, self._sharding), aux),
            }
            jax.block_until_ready(self.state)
        # every state leaf is row-shaped 2-D storage on the data's axis
        self._aux_specs = jax.tree.map(lambda a: P(SERVER_AXIS, None), aux)

        block_rows = self.block_rows
        updater = self.updater
        single = self.num_servers == 1

        def _local_lanes(ids):
            """Map the replicated global id vector to this shard's rows.

            Lanes owned elsewhere (and -1 padding) go to the trash row.
            On the 1-server fast path the shard index is the constant 0
            (these fns run outside shard_map there)."""
            s = 0 if single else lax.axis_index(SERVER_AXIS)
            shard_of = jnp.where(ids >= 0, ids // block_rows, -1)
            mine = shard_of == s
            safe = jnp.where(mine, ids - s * block_rows, block_rows)
            return mine, safe.astype(jnp.int32)
        self.device_local_lanes = _local_lanes  # for a caller's shard_map
        shard_rows = self.shard_rows

        def _aux_lanes(aux, safe, opt):
            """Per leaf, the rows of ``safe`` in that leaf: ``safe`` itself
            in shared state (shaped like the shard: momentum's smooth), and
            in per-worker state (updaters.base.worker_rows: this shard's
            rows of every worker, block after block) the same rows of the
            block of the worker that sent the Add — ``opt["worker_id"]``
            stays traced, one program serves every worker. Trash lanes go
            to the LEAF's last row, the last worker's trash row, which is
            where ops.rows' dense-run test looks for them."""
            lanes = {shard_rows: safe}
            for leaf in jax.tree.leaves(aux):
                if leaf.shape[0] not in lanes:
                    lanes[leaf.shape[0]] = jnp.where(
                        safe == block_rows, leaf.shape[0] - 1,
                        opt["worker_id"] * shard_rows + safe)
            return jax.tree.map(lambda leaf: lanes[leaf.shape[0]], aux)

        def _gather_aux(aux, lanes):
            return jax.tree.map(lambda leaf, idx: jnp.take(leaf, idx, axis=0),
                                aux, lanes)

        num_workers, num_servers = zoo.num_workers, self.num_servers

        def _update_full(state, delta, opt):
            new_data, new_aux = updater.update_worker(
                state["data"], state["aux"], delta, opt, num_workers,
                num_servers)
            return {"data": new_data, "aux": new_aux}

        self._update_full = jax.jit(_update_full, donate_argnums=(0,))

        # Fused path: aux-free elementwise updaters (default add, sgd) run
        # the whole server-side Add as ops.update_rows over the touched
        # rows: gather, ``combine`` fused elementwise, one write — no aux
        # gather/scatter and no updater.update call.
        # Foreign lanes carry their real deltas into this shard's trash row,
        # which therefore accumulates garbage; that's fine solely because
        # the trash row is don't-care (never read back: Get masks non-mine
        # lanes to 0, _from_storage strips it).
        fuse = updater.fusable and not jax.tree.leaves(aux)
        # merged engine Adds (ProcessAddRun) are sound for exactly the
        # LINEAR aux-free updaters: a window's batches apply as one
        # duplicate-safe scatter-add of combine_scale * deltas
        merge_scale = updater.combine_scale
        self._merge_adds = fuse and merge_scale is not None
        combine = updater.combine  # captured once: identity-stable jit key

        def _update_stateful(local_data, local_aux, safe, deltas, opt):
            # state is row-shaped like the data (one worker's rows of it
            # are len(ids) rows of a 2-D leaf), so rows and state take one
            # row op (ops/rows.py): the Pallas kernel at 128 lanes, XLA's
            # scatter wider, one dense run for all of them on one shard
            return ops.update_rows_with_state(
                local_data, local_aux, safe,
                _aux_lanes(local_aux, safe, opt), deltas,
                lambda rows, aux_rows, d: updater.update(rows, aux_rows, d,
                                                         opt),
                dense=single)

        def _update_rows_local(local_data, local_aux, ids, deltas, opt):
            _, safe = _local_lanes(ids)
            # dense=single: the runtime dense-run cond belongs to the
            # single-shard program only — inside a shard_map body it
            # defeats donation (whole-table copies; rows.py gather_rows)
            if fuse:
                return ops.update_rows(local_data, safe, deltas,
                                       combine, dense=single), local_aux
            return _update_stateful(local_data, local_aux, safe, deltas,
                                    opt)[:2]

        store_cols = self.store_cols

        # named scopes: stable names in the device trace's op metadata
        @jax.named_scope("table.update_rows")
        def _update_rows(state, ids, deltas, opt):
            if deltas.shape[-1] != store_cols:   # logical cols in, pad zeros
                deltas = jnp.pad(
                    deltas, ((0, 0), (0, store_cols - deltas.shape[-1])))
            if single:
                # 1-server fast path: identical lane semantics (pad lanes
                # -> trash row) without the shard_map wrapper/psum — the
                # single-chip case compiles a leaner program
                data, aux = _update_rows_local(state["data"], state["aux"],
                                               ids, deltas, opt)
                return {"data": data, "aux": aux}
            data, aux = jax.shard_map(
                _update_rows_local, mesh=self._mesh,
                in_specs=(P(SERVER_AXIS, None), self._aux_specs, P(), P(),
                          P()),
                out_specs=(P(SERVER_AXIS, None), self._aux_specs),
                check_vma=False,  # pallas_call outputs carry no vma info
            )(state["data"], state["aux"], ids, deltas, opt)
            return {"data": data, "aux": aux}

        self._update_rows = jax.jit(_update_rows, donate_argnums=(0,))

        @jax.named_scope("table.merged_add_rows")
        def _merged_add_rows(state, uniq_ids, deltas, inv, opt):
            """A window's stacked Add batches as ONE dispatch. The
            duplicate structure (unique ids + inverse mapping) is
            computed on the HOST (np.unique — XLA's sort was measured
            6x slower than numpy's on the CPU backend); the device does
            ONE segment-sum over the flattened delta payload and the
            normal fused row update at the UNIQUE bucket size. Sound
            because linear updaters sum — the combined batch rides the
            same update path as unmerged adds. Pad lanes (inverse 0
            pointing at a zero delta, uniq id -1 -> trash) are inert."""
            flat = deltas.reshape(-1, deltas.shape[-1])
            combined = jax.ops.segment_sum(
                flat, inv, num_segments=uniq_ids.shape[0])
            return _update_rows(state, uniq_ids, combined, opt)

        self._merged_add_rows = jax.jit(_merged_add_rows,
                                        donate_argnums=(0,))

        # -- compressed-wire consumers (compress="sparse"/"1bit") ------------
        # The worker ships the COMPRESSED payload; these jit'd consumers
        # reconstruct the dense delta ON DEVICE and run the normal row
        # update — the dense form never crosses the host<->device link.

        num_cols_c = num_cols

        def _consume_sparse(state, padded_ids, idx, val, opt):
            # idx addresses the flattened (row_bucket, cols) delta block;
            # pad lanes carry an out-of-range index (scatter drops OOB)
            size = padded_ids.shape[0] * num_cols_c
            dense = jnp.zeros((size,), val.dtype).at[idx].set(val)
            return _update_rows(state, padded_ids,
                                dense.reshape(padded_ids.shape[0],
                                              num_cols_c), opt)

        self._consume_sparse = jax.jit(_consume_sparse, donate_argnums=(0,))

        def _consume_1bit(state, padded_ids, packed, pos_means, neg_means,
                          opt):
            shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
            bits = ((packed[:, None] >> shifts) & 1).astype(jnp.bool_)
            lanes = bits.reshape(-1)[: padded_ids.shape[0] * num_cols_c]
            lanes = lanes.reshape(padded_ids.shape[0], num_cols_c)
            deltas = jnp.where(lanes, pos_means[:, None],
                               neg_means[:, None]).astype(
                state["data"].dtype)
            return _update_rows(state, padded_ids, deltas, opt)

        self._consume_1bit = jax.jit(_consume_1bit, donate_argnums=(0,))
        # Device plane: the same row-update program, un-jitted, for callers
        # that trace it into a larger computation (a training step or a
        # lax.scan over PS rounds) — on TPU this is how workers that live on
        # the same mesh as the store use the table without ever leaving HBM.
        # Signature: (state, padded_ids i32[bucket], deltas [bucket, cols],
        # opt = AddOption.as_jnp()) -> state.
        self.device_update_rows = _update_rows

        # Apply the access hook on the row path only when an updater
        # overrides it (identity for every reference updater,
        # updater.cpp:32) — the common case skips the aux gather.
        from multiverso_tpu.updaters.base import Updater as _UpdaterBase
        has_access = type(updater).access is not _UpdaterBase.access
        # the gather program carries no worker id (``_aux_lanes`` is given
        # no option there): the hook reads shared state at the rows it serves
        CHECK(not (has_access and updater.per_worker),
              "an access hook over per-worker state is not supported")

        num_cols_ = num_cols

        def _gather_rows_local(local_data, local_aux, ids):
            mine, safe = _local_lanes(ids)
            rows = ops.gather_rows(local_data, safe)
            if has_access:
                rows = updater.access(rows, _gather_aux(
                    local_aux, _aux_lanes(local_aux, safe, None)), None)
            # slice the storage pad off BEFORE the psum: only logical
            # columns ride ICI
            rows = jnp.where(mine[:, None], rows[:, :num_cols_], 0)
            if single:
                return rows  # no peers to sum with
            return lax.psum(rows, SERVER_AXIS)

        @jax.named_scope("table.gather_rows")
        def _gather_rows(data, aux, ids):
            if single:
                # 1-server fast path (see _update_rows)
                return _gather_rows_local(data, aux, ids)
            return jax.shard_map(
                _gather_rows_local, mesh=self._mesh,
                in_specs=(P(SERVER_AXIS, None), self._aux_specs, P()),
                out_specs=P(),
                check_vma=False,  # pallas_call outputs carry no vma info
            )(data, aux, ids)

        self._gather_rows = jax.jit(_gather_rows)
        # Device plane, get side: (data, aux, padded_ids) -> rows (replicated;
        # trash/foreign lanes return 0 and are summed across shards).
        self.device_gather_rows = _gather_rows

        # -- fused PS round: Add + Get of the same rows ----------------------
        # One traced verb for the reference's Add-then-Get-same-rows round
        # (test_matrix_perf.cpp:84-110): for fusable updaters the single
        # row read serves both halves (ops.update_gather_rows), saving a
        # full gather per round. (state, padded_ids, deltas, opt) ->
        # (state, rows) with the same masking/psum contract as
        # device_gather_rows.

        def _update_gather_local(local_data, local_aux, ids, deltas, opt):
            mine, safe = _local_lanes(ids)
            if fuse:
                data, rows = ops.update_gather_rows(local_data, safe,
                                                    deltas, combine,
                                                    dense=single)
                aux = local_aux
            else:
                # non-fused updaters already computed the post-update rows
                # — reuse them instead of a second full gather (duplicates
                # are caller-pre-combined, so per-lane new_rows are exact;
                # trash lanes are garbage and masked below)
                data, aux, rows = _update_stateful(local_data, local_aux,
                                                   safe, deltas, opt)
            if has_access:
                rows = updater.access(rows, _gather_aux(
                    aux, _aux_lanes(aux, safe, None)), None)
            rows = jnp.where(mine[:, None], rows[:, :num_cols_], 0)
            if single:
                return data, aux, rows
            return data, aux, lax.psum(rows, SERVER_AXIS)

        def _update_gather_rows(state, ids, deltas, opt):
            if deltas.shape[-1] != store_cols:
                deltas = jnp.pad(
                    deltas, ((0, 0), (0, store_cols - deltas.shape[-1])))
            if single:
                data, aux, rows = _update_gather_local(
                    state["data"], state["aux"], ids, deltas, opt)
                return {"data": data, "aux": aux}, rows
            data, aux, rows = jax.shard_map(
                _update_gather_local, mesh=self._mesh,
                in_specs=(P(SERVER_AXIS, None), self._aux_specs, P(), P(),
                          P()),
                out_specs=(P(SERVER_AXIS, None), self._aux_specs, P()),
                check_vma=False,
            )(state["data"], state["aux"], ids, deltas, opt)
            return {"data": data, "aux": aux}, rows

        self.device_update_gather_rows = _update_gather_rows

        # -- parts variants: the MULTI-PROCESS device plane ------------------
        # ids/deltas arrive as batch-sharded GLOBAL arrays
        # (device_place_batch) whose per-process slice is that process's
        # own batch. The traced round merges them on device: dedup_rows
        # combines duplicate ids across processes by summing deltas (the
        # host plane's np.add.at pre-combine contract, so every updater
        # is safe), and GSPMD inserts the gathers that replicate the
        # merged batch into the row program. Every process traces the
        # identical round (SPMD collective contract) — this is the
        # reference's "workers on every node reach every server shard"
        # (worker.cpp:30-79) with ICI as the wire instead of MPI.

        def _update_rows_parts(state, ids_parts, deltas_parts, opt):
            ids, deltas = ops.dedup_rows(ids_parts, deltas_parts)
            return _update_rows(state, ids, deltas, opt)

        self.device_update_rows_parts = _update_rows_parts
        self._update_rows_parts_j = jax.jit(_update_rows_parts,
                                            donate_argnums=(0,))

        def _gather_rows_parts(data, aux, ids_parts):
            # gather is duplicate-safe — no dedup; the sharded batch is
            # replicated by GSPMD on entry to the row program
            return _gather_rows(data, aux, ids_parts)

        self.device_gather_rows_parts = _gather_rows_parts
        self._gather_rows_parts_j = jax.jit(_gather_rows_parts)

    # -- storage layout (interleaved shard blocks + trash rows) -------------

    def _to_storage(self, full: np.ndarray) -> np.ndarray:
        """(num_rows, num_cols) logical -> (padded_rows, store_cols)
        storage (rows interleaved into shard blocks, cols zero-padded)."""
        out = np.zeros((self.num_servers, self.shard_rows, self.store_cols),
                       full.dtype)
        padded = np.zeros((self.num_servers * self.block_rows, self.num_cols),
                          full.dtype)
        padded[: self.num_rows] = full
        out[:, : self.block_rows, : self.num_cols] = padded.reshape(
            self.num_servers, self.block_rows, self.num_cols)
        return out.reshape(self.padded_rows, self.store_cols)

    def _from_storage(self, storage: np.ndarray) -> np.ndarray:
        """(padded_rows, store_cols) storage -> (num_rows, num_cols)
        logical."""
        blocks = storage.reshape(self.num_servers, self.shard_rows,
                                 self.store_cols)[:, : self.block_rows,
                                                  : self.num_cols]
        return blocks.reshape(-1, self.num_cols)[: self.num_rows]

    @property
    def _state(self):
        # read-only alias of ``state`` for benchmark/tools/aot_rounds_4c.py
        return self.state

    def _read_rows_union(self, union_ids: np.ndarray) -> np.ndarray:
        """Rows for an already-validated (and, multi-process, already
        cross-rank-agreed) id vector in ONE read: one padded gather —
        the merged read that batched window Gets
        (SparseMatrixTable.ProcessGetWindowParts) slice."""
        device_ids = self._device_ids(np.asarray(union_ids, np.int32))
        with crossing.call("_gather_rows"):
            rows = self._gather_rows(self.state["data"], self.state["aux"],
                                     device_ids)
        return np.asarray(self._take_rows(rows, len(union_ids)))

    # -- helpers ------------------------------------------------------------

    def _pad_ids(self, ids: np.ndarray,
                 bucket: Optional[int] = None) -> np.ndarray:
        if bucket is None:
            bucket = next_bucket(len(ids))
        out = np.full(bucket, -1, np.int32)
        out[: len(ids)] = ids
        return out

    # public for device-plane callers (pad lane = -1 -> trash row)
    pad_ids = _pad_ids

    def _place_small(self, host):
        """Host array(s) -> the device in ONE copy, in the sharding the
        row programs declare for ids and option scalars."""
        return crossing.place(host, self._put_small)

    def _put_small(self, host):
        if self._replicated is None:
            return jax.tree.map(jnp.asarray, host)
        return jax.device_put(host, self._replicated)

    def _take_rows(self, rows: jax.Array, n: int) -> np.ndarray:
        """The first ``n`` of a gather's bucket, on the host: the bucket
        crosses with its pad and the pad is dropped by a view of the host
        array, or a slice program drops it first where it is too large
        to carry (``_leaving_rows``)."""
        return crossing.take(_leaving_rows(rows, n),
                             self._zoo.mesh_ctx.fetch)[:n]

    def _verb_span(self, name: str, **args):
        """A verb's span under ``server.``; its ``args`` (the table's id
        and the caller's) are built only while a trace runs."""
        if ttrace.enabled():
            args["table_id"] = getattr(self, "table_id", -1)
        return ttrace.span(name, cat="server", args=args)

    def _device_ids(self, ids: np.ndarray) -> jax.Array:
        """A validated id vector padded ON THE HOST to its bucket (pad
        lane = -1 -> trash row; at most a quarter of 4 bytes an id) and
        copied once — no pad program, no resharding on entry."""
        return self._place_small(self._pad_ids(ids))

    #: distinct options whose device scalars a table keeps (FIFO): one a
    #: worker under a fixed rule; a learning-rate schedule is a miss a step
    _OPT_CACHE_SIZE = 64

    def _device_opt(self, option: Optional[AddOption] = None
                    ) -> Dict[str, jax.Array]:
        """``option.as_jnp()`` (None = the default option) without the
        five copies: the device scalars of each distinct option are built
        once, in one ``device_put``, and kept. They stay TRACED arguments
        of the row programs — a changed learning rate is a miss here,
        never a retrace there."""
        o = option or AddOption()
        key = (int(o.worker_id), float(o.momentum), float(o.learning_rate),
               float(o.rho), float(o.lambda_))
        opt = self._opt_cache.get(key)
        if opt is not None:
            tmetrics.counter("table.option_cache.hits").inc()
            return opt
        tmetrics.counter("table.option_cache.misses").inc()
        opt = self._place_small({
            "worker_id": np.int32(key[0]),
            "momentum": np.float32(key[1]),
            "learning_rate": np.float32(key[2]),
            "rho": np.float32(key[3]),
            "lambda_": np.float32(key[4]),
        })
        with self._opt_lock:    # engine applies and device-plane verbs
            while len(self._opt_cache) >= self._OPT_CACHE_SIZE:
                del self._opt_cache[next(iter(self._opt_cache))]
            self._opt_cache[key] = opt
        return opt

    def _check_ids(self, ids: np.ndarray) -> None:
        CHECK(ids.size > 0, "empty row id set")
        CHECK(self._ids_in_range(ids), "row id out of range")

    def _ids_in_range(self, ids: np.ndarray) -> bool:
        """A non-empty id vector inside the table (the run hooks decline
        on False where ``_check_ids`` raises)."""
        return (ids.size > 0 and int(ids.min()) >= 0
                and int(ids.max()) < self.num_rows)

    def _combine_duplicates(self, ids: np.ndarray, deltas: np.ndarray):
        """Pre-combine duplicate row ids (see module docstring)."""
        return _combine_duplicate_rows(ids, deltas, deltas.shape[1],
                                       deltas.dtype)

    # -- server verbs -------------------------------------------------------

    def _shared_row_ids(self, payloads) -> Optional[np.ndarray]:
        """The one id vector that every payload of a run names,
        validated, or None: a payload without ``row_ids`` (a whole-table
        Add) or ``compressed``, an id out of range, or an id array that
        is not the first's (``a is b or np.array_equal(a, b)``, which
        tells another length before it compares an entry; another order
        is another array; the first mismatch ends the test). Workers that push one shared
        set hand over one array object, so the common case compares
        nothing; where the arrays differ the cost is one comparison of
        a payload's ids."""
        shared = None
        for p in payloads:
            row_ids = p.get("row_ids")
            if row_ids is None or p.get("compressed") is not None:
                return None
            ids = np.asarray(row_ids, np.int32).ravel()
            if shared is None:
                if not self._ids_in_range(ids):
                    return None
                shared = ids
            elif not (ids is shared or np.array_equal(ids, shared)):
                return None
        return shared

    def ProcessAddSameRows(self, payloads) -> bool:
        """The same-rows run (base-class contract): a stretch of two or
        more row Adds whose payloads all name the SAME id array is
        summed on the host and applied as ONE lone Add. Workers that
        train synchronously push the same rows every step, so the bytes
        into the device, which bound a round's Adds, fall with the count
        of Adds summed. Accepts exactly when ``ProcessAddRun`` could
        (one process; a linear, aux-free updater; every payload with
        ``row_ids`` in range, none ``compressed``, ``values`` of the
        right size) AND the id arrays are equal (``_shared_row_ids``);
        everything is validated before anything is written. The deltas
        are summed in the table's dtype in message order into a FRESH
        array (no payload is written to): for a linear updater
        ``update(update(s, a), b) == update(s, a + b)``, the contract
        ``ProcessAddRun`` and ``multihost.sum_collective_add`` already
        rest on, and only the association of the additions differs from
        the Adds one by one, not the precision. The sum takes the lone
        Add's own path (``_combine_duplicates`` for repeats inside the
        id set, then ``_dispatch_rows``: ``_update_rows`` at the lone
        Add's bucket), so no program, shape or compile is new whatever
        the count of Adds, and one ``.merge`` and one ``.dispatch`` span
        cover the run. Subclass bookkeeping fires once a payload in
        message order with that payload's option, as in
        ``ProcessAddRun``. Counters ``table.add_run.summed`` (runs) and
        ``table.add_run.summed_adds`` (Adds in them)."""
        if (len(payloads) < 2 or multihost.world_size() > 1
                or not self._merge_adds):
            return False
        # both registered at 0 by the first run a table is offered
        summed_runs = tmetrics.counter("table.add_run.summed")
        summed_adds = tmetrics.counter("table.add_run.summed_adds")
        ids = self._shared_row_ids(payloads)
        if ids is None:
            return False
        with self._verb_span("server.table.add_run.merge",
                             adds=len(payloads)):
            deltas_list = []
            for p in payloads:
                values = np.asarray(p.get("values"), self.dtype)
                if values.size != ids.size * self.num_cols:
                    return False
                deltas_list.append(values.reshape(ids.size, self.num_cols))
            total = deltas_list[0] + deltas_list[1]
            for d in deltas_list[2:]:
                total += d
            uniq, total = self._combine_duplicates(ids, total)
        with self._verb_span("server.table.add_run.dispatch",
                             adds=len(payloads)):
            # option scalars are irrelevant to linear updaters
            self._dispatch_rows(uniq, total, None)
        summed_runs.inc()
        summed_adds.inc(len(payloads))
        for p in payloads:
            self._note_add_parts(p.get("option") or AddOption(), [ids])
        return True

    def ProcessAddRun(self, payloads) -> bool:
        """Engine add-coalescing (base-class contract): merge a window's
        row-set Adds into ONE device dispatch. A run whose payloads all
        name the same id array is summed on the host and applied as one
        lone Add (``ProcessAddSameRows``, tried first: a quarter of the
        bytes cross for a run of four, and no program of the run's
        own); at the first id array that differs the run is stacked
        instead — concat the batches,
        pre-combine duplicates ACROSS the merged adds (np.add.at), one
        jit'd update. Sound exactly when the updater declares itself
        LINEAR (``combine_scale is not None``): update(data, delta) ==
        data + c*delta with c a class constant and AddOption scalars
        ignored by contract (updaters/base.py combine_scale) — so
        pre-summing a window equals sequential application whatever
        per-message options rode along. Declines multihost jobs (the
        collective-merge protocol owns those), whole-table adds,
        non-linear/aux updaters, and anything that fails validation
        (the per-message path then reports precise errors)."""
        if multihost.world_size() > 1 or not self._merge_adds:
            return False
        if self.ProcessAddSameRows(payloads):
            return True
        # a window's run of Adds in two named halves: .merge is host
        # numpy (validation, stacking, np.unique, padding), .dispatch the
        # host-to-device copies (.place) and the merged program's .call
        with self._verb_span("server.table.add_run.merge",
                             adds=len(payloads)):
            ids_list, deltas_list = [], []
            for p in payloads:
                row_ids = p.get("row_ids")
                if row_ids is None or p.get("compressed") is not None:
                    return False
                ids = np.asarray(row_ids, np.int32).ravel()
                if not self._ids_in_range(ids):
                    return False
                values = np.asarray(p.get("values"), self.dtype)
                if values.size != ids.size * self.num_cols:
                    return False
                ids_list.append(ids)
                deltas_list.append(values.reshape(len(ids), self.num_cols))
            if len({a.shape for a in deltas_list}) != 1:
                # mixed batch shapes would mint a fresh compile per
                # window composition — the per-message path is
                # cheaper than that
                return False
            # option scalars are irrelevant to linear updaters
            # (default/sgd ignore them), so runs merge regardless of
            # per-message options. The batch count quantizes to a
            # power of two and the unique-id count to the bucket
            # ladder, so the jit cache holds a bounded shape set
            # however the engine's windows race the producers.
            n, k = len(ids_list), ids_list[0].size
            nb = 1 << (n - 1).bit_length()
            if nb * k * 4 > ops.rows.SMEM_IDS_BYTES:
                # the merged id vector must fit the Pallas SMEM
                # prefetch budget (shared constant, ops/rows.py) —
                # huge windows process per-message so they keep the
                # row-DMA fast path
                return False
            ids = np.full((nb, k), -1, np.int32)
            deltas = np.zeros((nb, k, self.num_cols), self.dtype)
            for i, (a, d) in enumerate(zip(ids_list, deltas_list)):
                ids[i] = a
                deltas[i] = d
            uniq, inv = np.unique(ids.reshape(-1), return_inverse=True)
            # POWER-OF-TWO bucket (coarser than the ladder): the
            # unique count varies continuously with window overlap,
            # and every distinct bucket is a compile of this table's
            # merged program — pow2 caps the shape set at log2(window)
            # sizes, all warmable up front
            uniq_p = self._pad_ids(
                uniq, max(8, 1 << (len(uniq) - 1).bit_length()))
        with self._verb_span("server.table.add_run.dispatch",
                             adds=len(payloads)):
            operands = (crossing.place(uniq_p), crossing.place(deltas),
                        crossing.place(inv.astype(np.int32)),
                        self._device_opt())
            with crossing.call("_merged_add_rows"):
                # mv-lint: ok(cross-domain-state): one plane per table — the worker-domain writer is the device-plane collective verb path (lockstep app-thread calls), and a device-plane table never takes engine window applies concurrently
                self.state = self._merged_add_rows(self.state, *operands)
        # subclass bookkeeping fires per payload in message order, exactly
        # like the per-message path (SparseMatrixTable's freshness bits
        # must see every add's id set + worker attribution)
        for p, a in zip(payloads, ids_list):
            self._note_add_parts(p.get("option") or AddOption(), [a])
        return True

    def _process_add_compressed(self, comp: dict, option: AddOption) -> None:
        """Apply a worker-compressed Add: the payload stays compressed
        until it is ON DEVICE (the jit'd consumers reconstruct + update
        in one program). Multihost falls back to host decompression —
        the collective-merge protocol owns that path."""
        ids = np.asarray(comp["row_ids"], np.int32).ravel()
        self._check_ids(ids)
        if multihost.world_size() > 1:
            # BSP/direct multi-process path: host-decompress, then the
            # normal collective row Add (the windowed engine routes its
            # multi-process compressed Adds through ProcessAddParts)
            ids, deltas = self._decompress_payload({"compressed": comp})
            return self.ProcessAdd(deltas, option, row_ids=ids)
        self._consume_compressed_on_device(comp, option)
        self._note_add_parts(option, [ids])

    def _consume_compressed_on_device(self, comp: dict,
                                      option: AddOption) -> None:
        """Reconstruct + apply ONE compressed payload in-trace (the
        jit'd consumers); updates wire accounting. Fires NO subclass
        note — callers own the (exactly-once, rank-ordered) note."""
        ids = np.asarray(comp["row_ids"], np.int32).ravel()
        self._check_ids(ids)
        kind = comp["kind"]
        padded = self._pad_ids(ids)
        dense_bytes = ids.size * self.num_cols * self.dtype.itemsize
        if kind == "sparse":
            idx = np.asarray(comp["idx"], np.int32)
            val = np.asarray(comp["val"], self.dtype)
            nb = next_bucket(max(len(idx), 1))
            # pad index = out-of-range: the device scatter DROPS it
            idx_p = np.full(nb, len(padded) * self.num_cols, np.int32)
            idx_p[: len(idx)] = idx
            val_p = np.zeros(nb, self.dtype)
            val_p[: len(val)] = val
            self.state = self._consume_sparse(
                self.state, jnp.asarray(padded), jnp.asarray(idx_p),
                jnp.asarray(val_p), self._device_opt(option))
            self._note_wire(dense_bytes, idx_p.nbytes + val_p.nbytes)
        else:
            packed = np.asarray(comp["packed"], np.uint8)
            CHECK(packed.size * 8 >= len(padded) * self.num_cols,
                  "1bit payload shorter than the padded lane count")
            pos = np.zeros(len(padded), np.float32)
            pos[: len(ids)] = comp["pos"]
            neg = np.zeros(len(padded), np.float32)
            neg[: len(ids)] = comp["neg"]
            self.state = self._consume_1bit(
                self.state, jnp.asarray(padded), jnp.asarray(packed),
                jnp.asarray(pos), jnp.asarray(neg),
                self._device_opt(option))
            self._note_wire(dense_bytes,
                            packed.nbytes + pos.nbytes + neg.nbytes)

    def _note_wire(self, dense_bytes: int, payload_bytes: int) -> None:
        """Record one compressed payload's wire economics, locally
        (``wire_stats``, which the tests read) and in the telemetry
        registry."""
        from multiverso_tpu.telemetry import metrics as tmetrics
        self.wire_stats["dense_bytes"] += dense_bytes
        self.wire_stats["payload_bytes"] += payload_bytes
        tmetrics.counter("wire.compress.dense_bytes").inc(dense_bytes)
        tmetrics.counter("wire.compress.payload_bytes").inc(payload_bytes)

    def _note_add_parts(self, option: AddOption, parts) -> None:
        """Hook: every rank's id set (None = whole table) of the applied
        collective Add, in rank order — fires AFTER the data update so a
        rejected add cannot desynchronize subclass bookkeeping.
        SparseMatrixTable overrides this for its freshness bits (and
        calls back up). Round 17: the replica plane's publish journal
        rides the same hook — every Add path already funnels here, so
        one mark site covers blocking, windowed, merged-run, device-
        wire and compressed applies alike."""
        journal = self._pub_journal
        if journal is not None:
            for part_ids in parts:
                journal.mark_rows(part_ids)

    def ProcessAdd(self, values: Optional[np.ndarray] = None,
                   option: AddOption = None,
                   row_ids: Optional[np.ndarray] = None,
                   compressed: Optional[dict] = None) -> None:
        if compressed is not None:
            return self._process_add_compressed(compressed,
                                                option or AddOption())
        if row_ids is None:
            values = np.asarray(values, self.dtype).reshape(self.num_rows,
                                                            self.num_cols)
            # multihost: sum the per-process deltas of this collective Add
            # (reference semantics — every worker's Add accumulates).
            # (The windowed engine routes multi-process Adds through
            # ProcessAddParts — this collective remains for the BSP
            # engine and direct callers.)
            values, parts = multihost.sum_collective_add(option, values,
                                                         with_parts=True)
            self._apply_summed_full(values, option, parts)
            return
        # a lone row Add is a run of one: the same two span names as
        # ProcessAddRun (the second half is in _apply_merged_rows)
        with ttrace.span("server.table.add_run.merge", cat="server"):
            ids = np.asarray(row_ids, np.int32).ravel()
            deltas = np.asarray(values, self.dtype).reshape(len(ids),
                                                            self.num_cols)
            self._check_ids(ids)
            # multihost: merge every process's (ids, deltas) batch of this
            # collective Add — each process may push different rows; after
            # the merge all processes issue identical device programs over
            # identical data (identity single-process)
            (ids, deltas), parts = multihost.merge_collective_add(
                option, ids, deltas, with_parts=True)
            self._check_ids(ids)  # every rank's part, on every replica
        self._apply_merged_rows(ids, deltas, option, parts)

    def _apply_summed_full(self, values: np.ndarray, option: AddOption,
                           parts) -> None:
        """Apply an (already cross-rank summed) whole-table delta."""
        delta = self._zoo.mesh_ctx.place(self._to_storage(values),
                                         self._sharding)
        self.state = self._update_full(self.state, delta,
                                       self._device_opt(option))
        self._note_add_parts(option, parts)

    def _apply_merged_rows(self, ids: np.ndarray, deltas: np.ndarray,
                           option: AddOption, parts) -> None:
        """Apply an (already cross-rank merged, validated) row batch."""
        with ttrace.span("server.table.add_run.merge", cat="server"):
            ids, deltas = self._combine_duplicates(ids, deltas)
        with ttrace.span("server.table.add_run.dispatch", cat="server"):
            self._dispatch_rows(ids, deltas, option)
        self._note_add_parts(option, parts)

    def _dispatch_rows(self, ids: np.ndarray, deltas: np.ndarray,
                       option: Optional[AddOption]) -> None:
        """A lone Add's device half, inside the caller's ``.dispatch``
        span: distinct ids and their deltas across, the update."""
        # ship exact-size deltas; pad them to the bucket on device
        padded_ids = self._device_ids(ids)
        deltas = _place_rows(deltas, padded_ids.shape[0])
        opt = self._device_opt(option)
        with crossing.call("_update_rows"):
            self.state = self._update_rows(self.state, padded_ids,
                                           deltas, opt)

    # -- windowed-engine parts hooks (round 5; tables/base.py contract) -----
    # One window exchange already delivered EVERY rank's payloads — these
    # hooks merge and apply with zero further host collectives. Every
    # rank computes from identical parts, so validation failures raise
    # identically everywhere (state can't diverge).

    def _prep_add_parts(self, parts):
        """Validate + normalize one collective Add's per-rank payloads ->
        (option, kind, per-rank (ids, deltas)); kind in {'whole','rows'}.
        Compressed payloads are handled by _mh_add_compressed_parts."""
        opts = self._check_parts_options(parts)
        whole = [p.get("row_ids") is None and p.get("compressed") is None
                 for p in parts]
        CHECK(all(whole) or not any(whole),
              "collective Add mixes whole-table and row payloads across "
              "processes")
        if all(whole):
            vals = [np.asarray(p["values"], self.dtype).reshape(
                self.num_rows, self.num_cols) for p in parts]
            return opts[0], "whole", vals
        prepped = []
        for p in parts:
            ids = np.asarray(p["row_ids"], np.int32).ravel()
            self._check_ids(ids)
            deltas = np.asarray(p["values"], self.dtype).reshape(
                len(ids), self.num_cols)
            prepped.append((ids, deltas))
        return opts[0], "rows", prepped

    def ProcessAddParts(self, parts, my_rank: int) -> None:
        if any(p.get("compressed") is not None for p in parts):
            return self._mh_add_compressed_parts(parts)
        option, kind, prepped = self._prep_add_parts(parts)
        if kind == "whole":
            summed = prepped[0].copy()
            for v in prepped[1:]:
                summed += v
            self._apply_summed_full(summed, option, [None] * len(parts))
            return
        ids = np.concatenate([i for i, _ in prepped])
        deltas = np.concatenate([d for _, d in prepped])
        self._apply_merged_rows(ids, deltas, option,
                                [i for i, _ in prepped])

    def _mh_add_compressed_parts(self, parts) -> None:
        """One collective Add where at least one rank shipped a
        COMPRESSED payload (ranks may legitimately mix: the sparse
        filter falls back to dense per rank on density). The exchange
        already moved the compressed bytes — exactly what the mode
        exists to shrink; here every rank reconstructs IN-TRACE via the
        table's jit'd consumers, applied per rank-part in rank order.
        Sound because compressed tables with linear updaters commute
        (update(update(s,a),b) == update(s,a+b)); non-linear updaters
        decompress on host and apply the merged batch (the documented
        duplicate pre-combine contract needs the whole batch at once)."""
        opts = self._check_parts_options(parts)
        option = opts[0]
        if self.updater.combine_scale is None:
            # non-linear: host-decompress every rank's payload, merge,
            # one device apply (still zero extra host collectives)
            merged_ids, merged_deltas = [], []
            for p in parts:
                ids, deltas = self._decompress_payload(p)
                merged_ids.append(ids)
                merged_deltas.append(deltas)
            self._apply_merged_rows(np.concatenate(merged_ids),
                                    np.concatenate(merged_deltas), option,
                                    merged_ids)
            return
        # validate EVERY rank's part before any mutation (determinism:
        # a bad part fails the whole position identically everywhere)
        rank_ids = []
        for p in parts:
            comp = p.get("compressed")
            ids = np.asarray((comp or p)["row_ids"], np.int32).ravel()
            self._check_ids(ids)
            rank_ids.append(ids)
        # linear: reconstruct every rank's block IN-TRACE and sum into
        # the union row batch on device, then apply once — the same
        # unique-id set, pairwise rank-order sums, and row program as
        # the uncompressed merged apply, so the exact sparse wire stays
        # bit-identical to it (the lossy 1bit wire converges via its
        # error feedback as usual)
        cols = self.num_cols
        union = np.unique(np.concatenate(rank_ids)).astype(np.int32)
        bucket = next_bucket(len(union))
        combined = jnp.zeros((bucket, cols), self.dtype)
        for p, ids in zip(parts, rank_ids):
            comp = p.get("compressed")
            nb_r = next_bucket(len(ids))
            inv = np.full(nb_r, bucket, np.int32)   # pad -> OOB drop
            inv[: len(ids)] = np.searchsorted(union, ids)
            inv_j = jnp.asarray(inv)
            if comp is None:
                # pre-combine within-rank duplicates on host (device
                # scatter order among duplicates is undefined; np.add.at
                # order matches the uncompressed merge)
                u_ids, u_deltas = self._combine_duplicates(
                    ids, np.asarray(p["values"], self.dtype).reshape(
                        len(ids), cols))
                nb_r = next_bucket(len(u_ids))
                inv = np.full(nb_r, bucket, np.int32)
                inv[: len(u_ids)] = np.searchsorted(union, u_ids)
                inv_j = jnp.asarray(inv)
                block = np.zeros((nb_r, cols), self.dtype)
                block[: len(u_ids)] = u_deltas
                combined = _acc_dense_part(combined, inv_j,
                                           jnp.asarray(block))
                continue
            dense_bytes = ids.size * cols * self.dtype.itemsize
            if comp["kind"] == "sparse":
                idx = np.asarray(comp["idx"], np.int32)
                val = np.asarray(comp["val"], self.dtype)
                nb = next_bucket(max(len(idx), 1))
                idx_p = np.full(nb, nb_r * cols, np.int32)  # pad: drop
                idx_p[: len(idx)] = idx
                val_p = np.zeros(nb, self.dtype)
                val_p[: len(val)] = val
                combined = _acc_sparse_part(
                    combined, inv_j, jnp.asarray(idx_p),
                    jnp.asarray(val_p), rows=nb_r, cols=cols)
                self._note_wire(dense_bytes, idx_p.nbytes + val_p.nbytes)
            else:
                packed = np.asarray(comp["packed"], np.uint8)
                CHECK(packed.size * 8 >= nb_r * cols,
                      "1bit payload shorter than the padded lane count")
                pos = np.zeros(nb_r, np.float32)
                pos[: len(ids)] = comp["pos"]
                neg = np.zeros(nb_r, np.float32)
                neg[: len(ids)] = comp["neg"]
                combined = _acc_1bit_part(
                    combined, inv_j, jnp.asarray(packed),
                    jnp.asarray(pos), jnp.asarray(neg), rows=nb_r,
                    cols=cols)
                self._note_wire(dense_bytes,
                                packed.nbytes + pos.nbytes + neg.nbytes)
        self.state = self._update_rows(
            self.state, jnp.asarray(self._pad_ids(union, bucket)), combined,
            self._device_opt(option))
        # ONE rank-ordered note for the whole collective Add (sparse
        # freshness attributes each rank's part to its global worker)
        self._note_add_parts(option, rank_ids)

    def _decompress_payload(self, p):
        """A rank's Add payload -> host (ids, deltas), compressed or not."""
        comp = p.get("compressed")
        if comp is None:
            ids = np.asarray(p["row_ids"], np.int32).ravel()
            self._check_ids(ids)
            return ids, np.asarray(p["values"], self.dtype).reshape(
                len(ids), self.num_cols)
        from multiverso_tpu.utils.quantization import SparseFilter
        ids = np.asarray(comp["row_ids"], np.int32).ravel()
        self._check_ids(ids)
        if comp["kind"] == "sparse":
            deltas = SparseFilter().decompress(
                True, comp["idx"], comp["val"], len(ids) * self.num_cols,
                self.dtype).reshape(len(ids), self.num_cols)
        else:
            lanes = np.unpackbits(comp["packed"])[: len(ids) * self.num_cols]
            lanes = lanes.astype(bool).reshape(len(ids), self.num_cols)
            deltas = np.where(lanes, comp["pos"][:, None],
                              comp["neg"][:, None]).astype(self.dtype)
        return ids, deltas

    def ProcessAddRunParts(self, positions, my_rank: int) -> bool:
        """Cross-rank add-coalescing: merge a window's collective row
        Adds (all positions x all ranks) into ONE apply. Linear aux-free
        updaters only (the single-proc ProcessAddRun contract); declines
        whole-table/compressed payloads and validation doubts so the
        per-position path reports precise errors."""
        if not self._merge_adds:
            return False
        all_ids, all_deltas, noted = [], [], []
        for parts in positions:
            opts = self._norm_parts_options(parts)
            if not all(o == opts[0] for o in opts):
                return False
            rank_ids = []
            for p in parts:
                row_ids = p.get("row_ids")
                if row_ids is None or p.get("compressed") is not None:
                    return False
                ids = np.asarray(row_ids, np.int32).ravel()
                if not self._ids_in_range(ids):
                    return False
                values = np.asarray(p.get("values"), self.dtype)
                if values.size != ids.size * self.num_cols:
                    return False
                all_ids.append(ids)
                all_deltas.append(values.reshape(len(ids), self.num_cols))
                rank_ids.append(ids)
            noted.append((opts[0], rank_ids))
        ids = np.concatenate(all_ids)
        deltas = np.concatenate(all_deltas)
        ids, deltas = self._combine_duplicates(ids, deltas)
        padded_ids = self._device_ids(ids)
        self.state = self._update_rows(
            self.state, padded_ids,
            _pad_rows(jnp.asarray(deltas), padded_ids.shape[0]),
            self._device_opt())
        # subclass bookkeeping fires per position in window order with
        # per-rank id sets (SparseMatrixTable freshness needs each add's
        # attribution), exactly like the per-position path
        for option, rank_ids in noted:
            self._note_add_parts(option, rank_ids)
        return True

    # -- DEVICE-wire transport (round 6; tables/base.py contract) -----------

    def device_wire_add_ok(self, payload) -> bool:
        """Row-set Adds with a plain dense delta can ride the device
        wire: the ids (tiny) cross the host exchange, the delta block
        moves through the batch-sharded parts round (place_parts + ONE
        traced collective update — _update_rows_parts_j, the same
        program device_apply_rows runs). Whole-table payloads decline
        (their replicated-sum shape isn't what the parts round models),
        and COMPRESSED TABLES decline entirely: compression already
        shrank the host bytes (deferring would forfeit exactly that),
        and its dense fallback is data-dependent PER RANK — this rank's
        dense payload may sit at the same position as a peer's
        compressed one, which only the host path's mixed-parts apply
        handles."""
        return (self.compress is None
                and payload.get("row_ids") is not None
                and payload.get("compressed") is None
                and isinstance(payload.get("values"), np.ndarray))

    def ProcessAddPartsDevice(self, parts, my_rank: int) -> None:
        """One collective row Add whose values ride the device wire.
        Every rank validates every rank's metadata (ids + declared
        value shapes) so failures raise identically everywhere; the
        shared bucket derives from the exchanged shapes — no extra host
        round."""
        opts = self._check_parts_options(parts)
        rank_ids = []
        for p in parts:
            ids = np.asarray(p["row_ids"], np.int32).ravel()
            self._check_ids(ids)
            v = p["values"]
            size = v.size if isinstance(v, wire.DeferredArray) \
                else np.asarray(v).size
            CHECK(size == ids.size * self.num_cols,
                  "device-wire Add size mismatch")
            rank_ids.append(ids)
        mine = parts[my_rank]["values"]
        local_vals = mine.local if isinstance(mine, wire.DeferredArray) \
            else mine
        CHECK(local_vals is not None,
              "device-wire Add lost its local values (engine bug)")
        # shared bucket from the EXCHANGED metadata — every rank computes
        # the same rung, so the collective parts program traces once
        bucket = parts_bucket(max(len(i) for i in rank_ids),
                              local_device_count(self._mesh))
        local_vals = np.asarray(local_vals, self.dtype).reshape(
            len(rank_ids[my_rank]), self.num_cols)
        gids, gdeltas = self.device_place_batch(rank_ids[my_rank],
                                                local_vals, bucket=bucket)
        self.state = self._update_rows_parts_j(self.state, gids, gdeltas,
                                               self._device_opt(opts[0]))
        self._note_add_parts(opts[0], rank_ids)

    def ProcessAddRunPartsDevice(self, positions, my_rank: int) -> bool:
        """Merged DEVICE-wire run (tables/base.py contract): a window's
        deferred row Adds concatenate per rank — from the EXCHANGED
        metadata, so every rank builds the identical batch — and apply
        in ONE batch-sharded parts round instead of one traced
        collective per position (dedup_rows pre-combines duplicate ids
        across positions AND ranks by summing). Linear aux-free
        updaters only (the ProcessAddRunParts contract); declines on
        validation doubt so the per-position device path reports
        precise errors. Subclass bookkeeping fires per position in
        window order after the merged apply (the SparseMatrixTable
        soundness note)."""
        if not self._merge_adds:
            return False
        n_ranks = len(positions[0])
        cat_ids: list = [[] for _ in range(n_ranks)]
        my_vals, noted = [], []
        for parts in positions:
            opts = self._norm_parts_options(parts)
            if not all(o == opts[0] for o in opts):
                return False
            rank_ids = []
            for r, p in enumerate(parts):
                row_ids = p.get("row_ids")
                if row_ids is None or p.get("compressed") is not None:
                    return False
                ids = np.asarray(row_ids, np.int32).ravel()
                if not self._ids_in_range(ids):
                    return False
                v = p.get("values")
                size = v.size if isinstance(v, wire.DeferredArray) \
                    else np.asarray(v).size
                if size != ids.size * self.num_cols:
                    return False
                if r == my_rank:
                    local = v.local if isinstance(v, wire.DeferredArray) \
                        else v
                    CHECK(local is not None,
                          "device-wire Add lost its local values "
                          "(engine bug)")
                    my_vals.append(np.asarray(local, self.dtype).reshape(
                        len(ids), self.num_cols))
                cat_ids[r].append(ids)
                rank_ids.append(ids)
            noted.append((opts[0], rank_ids))
        cat_ids = [np.concatenate(i) for i in cat_ids]
        bucket = parts_bucket(max(len(i) for i in cat_ids),
                              local_device_count(self._mesh))
        gids, gdeltas = self.device_place_batch(cat_ids[my_rank],
                                                np.concatenate(my_vals),
                                                bucket=bucket)
        # linear contract: option scalars are ignored, exactly like the
        # merged host run's single default-option apply
        self.state = self._update_rows_parts_j(self.state, gids, gdeltas,
                                               self._device_opt())
        for option, rank_ids in noted:
            self._note_add_parts(option, rank_ids)
        return True

    def _full_logical(self) -> np.ndarray:
        """The whole logical matrix on THIS host. Multi-process: XLA
        replicates over ICI (no host-collective reassembly round)."""
        if multihost.world_size() > 1:
            if not hasattr(self, "_access_full_repl"):
                from jax.sharding import NamedSharding

                def _full(state):
                    return self.updater.access(state["data"], state["aux"],
                                               None)

                self._access_full_repl = jax.jit(
                    _full, out_shardings=NamedSharding(self._mesh, P()))
            return self._from_storage(
                np.asarray(self._access_full_repl(self.state)))
        data = self.updater.access(self.state["data"], self.state["aux"],
                                   None)
        return self._from_storage(self._zoo.mesh_ctx.fetch(data))

    def _note_row_access(self, ids) -> None:
        """Feed one Get's row ids to the ``-mv_row_sketch`` access-skew
        sketch (telemetry/sketch.py note_table_access — the one hook
        shared with the KV family since round 13; the off path is ONE
        cached int read). Engine-thread updates; the /metrics
        top-share gauge refreshes every 32 notes, not per Get."""
        fam = ("sparse" if "sparse" in type(self).__name__.lower()
               else "matrix")
        tsketch.note_table_access(self, ids, fam)

    def ProcessGetWindowParts(self, positions, my_rank: int):
        """Cross-rank get-dedup: serve a window segment's Gets from ONE
        merged read: one union gather (or one replicated full read when
        any request is whole-table) serves every position."""
        results: list = []
        # validate EVERY rank's ids per position; a bad position fails
        # deterministically everywhere and drops out of the union
        pos_ids: list = []
        any_whole = False
        for parts in positions:
            try:
                rank_ids = []
                for p in parts:
                    if p.get("row_ids") is None:
                        rank_ids.append(None)
                        any_whole = True
                    else:
                        ids = np.asarray(p["row_ids"], np.int32).ravel()
                        self._check_ids(ids)
                        rank_ids.append(ids)
                pos_ids.append(rank_ids)
            except Exception as exc:
                pos_ids.append(exc)
        for rank_ids in pos_ids:
            if (not isinstance(rank_ids, Exception)
                    and rank_ids[my_rank] is not None):
                self._note_row_access(rank_ids[my_rank])
        if any_whole:
            full = self._full_logical()
            for parts, rank_ids in zip(positions, pos_ids):
                if isinstance(rank_ids, Exception):
                    results.append(rank_ids)
                elif rank_ids[my_rank] is None:
                    results.append(full.copy())
                else:
                    results.append(full[rank_ids[my_rank]])
            return results
        union_list = [ids for rank_ids in pos_ids
                      if not isinstance(rank_ids, Exception)
                      for ids in rank_ids]
        if not union_list:
            return pos_ids        # every position failed validation
        union = np.unique(np.concatenate(union_list)).astype(np.int32)
        rows = self._gather_rows(self.state["data"], self.state["aux"],
                                 self._device_ids(union))
        host_rows = np.asarray(rows[: len(union)])
        for rank_ids in pos_ids:
            if isinstance(rank_ids, Exception):
                results.append(rank_ids)
            else:
                mine = rank_ids[my_rank]
                results.append(host_rows[np.searchsorted(union, mine)])
        return results

    def ProcessGetParts(self, parts, my_rank: int):
        """One collective Get from exchanged parts: the union is known
        locally — no union collective."""
        p = parts[my_rank]
        if any(q.get("row_ids") is None for q in parts):
            full = self._full_logical()
            if p.get("row_ids") is None:
                return full
            ids = np.asarray(p["row_ids"], np.int32).ravel()
            self._check_ids(ids)
            return full[ids]
        rank_ids = []
        for q in parts:
            ids = np.asarray(q["row_ids"], np.int32).ravel()
            self._check_ids(ids)
            rank_ids.append(ids)
        union = np.unique(np.concatenate(rank_ids)).astype(np.int32)
        return self.ProcessGet(p.get("option") or GetOption(),
                               row_ids=rank_ids[my_rank], _union=union)

    def ProcessGet(self, option: GetOption,
                   row_ids: Optional[np.ndarray] = None,
                   _union: Optional[np.ndarray] = None):
        """``_union``: a subclass that already knows every process's id set
        of this collective Get (SparseMatrixTable computes all ranks' stale
        sets for its lockstep bits) passes the precomputed union so the
        id sets don't ride a second host collective."""
        if row_ids is None:
            # multihost: XLA-replicated read (no host reassembly round)
            return self._full_logical()
        # the blocking Get's own span (the BSP server's every Get; the
        # windowed engine's Gets are ProcessGetAsync's): its copy in,
        # launch, wait and copy back show as server.table.get.place /
        # .call / .wait / .take
        with ttrace.span("server.table.get", cat="server"):
            ids = np.asarray(row_ids, np.int32).ravel()
            self._check_ids(ids)
            self._note_row_access(ids)
            union = (_union if _union is not None
                     else multihost.union_collective_ids(ids))
            if union is not None:
                # each process may request different rows of this
                # collective Get: gather the union with one identical
                # program everywhere, then slice this process's rows out
                # of the union result
                union = union.astype(np.int32)
                device_ids = self._device_ids(union)
                with crossing.call("_gather_rows"):
                    rows = self._gather_rows(self.state["data"],
                                             self.state["aux"], device_ids)
                host_rows = self._take_rows(rows, len(union))
                return host_rows[np.searchsorted(union, ids)]
            device_ids = self._device_ids(ids)
            with crossing.call("_gather_rows"):
                rows = self._gather_rows(self.state["data"],
                                         self.state["aux"], device_ids)
            return self._take_rows(rows, len(ids))

    def ProcessGetAsync(self, option: GetOption = None, row_ids=None):
        """Two-phase Get (base-class contract, tables/base.py): dispatch
        the gather + start the device->host copy now, fetch in finalize —
        the engine overlaps a window of these so queued host Gets pay one
        pipelined RTT instead of one each. What starts its copy is the
        gather's bucket itself, pad and all, and the finalize replies the
        first ``len(row_ids)`` rows as a view of the host array: one
        launch a Get (``_leaving_rows`` says when a slice program cuts
        the pad first). The gather's output is a fresh buffer, so an Add
        later in the window that donates the state cannot reach it."""
        if multihost.world_size() > 1:
            return None  # collective fetch/union — keep the sync path
        if row_ids is None:
            data = self.updater.access(self.state["data"], self.state["aux"],
                                       None)
            if data is self.state["data"]:
                # identity access returns the LIVE state buffer; an Add
                # drained later in the same pipeline window donates it
                # (donate_argnums) — finalize would read a deleted array.
                # Snapshot to a fresh buffer before the async copy.
                with crossing.call("copy"):
                    data = jnp.copy(data)
            data.copy_to_host_async()
            return lambda: self._from_storage(crossing.take(data))
        with ttrace.span("server.table.get.prepare", cat="server"):
            ids = np.asarray(row_ids, np.int32).ravel()
            self._check_ids(ids)
            self._note_row_access(ids)
        with ttrace.span("server.table.get.dispatch", cat="server"):
            device_ids = self._device_ids(ids)
            with crossing.call("_gather_rows"):
                rows = self._gather_rows(self.state["data"],
                                         self.state["aux"], device_ids)
            n = len(ids)
            leaving = _leaving_rows(rows, n)
            leaving.copy_to_host_async()
        # run by the engine inside server.window.finalize: .wait, .take
        return lambda: crossing.take(leaving)[:n]

    # -- eager device plane (public) ----------------------------------------
    # device_gather_rows / device_update_rows above are the TRACEABLE hooks
    # (scan them into a jit'd step — examples/device_plane.py);
    # these two are their eager siblings for callers that want per-block
    # dispatch with host-plane validation but no host round-trip of the
    # row data (e.g. the WordEmbedding communicator's -device_plane path).
    # The device plane bypasses the engine: no single-writer arbitration —
    # the caller owns the table while using it. Multi-process, the verbs
    # are COLLECTIVE (every process calls them in lockstep, each passing
    # its OWN batch); the per-process batches merge on device through the
    # parts round — nothing rides a host collective except the one-int
    # bucket agreement, and duplicate ids across processes combine by sum
    # exactly like the host plane's collective merge.

    def device_place_batch(self, row_ids, deltas=None, *, bucket=None):
        """THIS process's (ids[, deltas]) batch -> batch-sharded global
        arrays for the parts verbs. Collective multi-process. Every
        process must use the same ``bucket`` (pass it explicitly in
        scan-style loops; ``None`` agrees on parts_bucket of the global
        max batch via one tiny host allgather). Pad lanes are -1/zero.
        Device-resident deltas stay in HBM (place_parts splits them
        across this process's devices with on-device slices)."""
        ids = np.asarray(row_ids, np.int32).ravel()
        self._check_ids(ids)
        nproc = multihost.world_size()
        local_dev = local_device_count(self._mesh)
        if bucket is None:
            bucket = parts_bucket(max(
                multihost.host_allgather_objects_capped(
                    len(ids), "matrix_dpb")), local_dev)
        CHECK(len(ids) <= bucket,
              f"device_place_batch: batch {len(ids)} exceeds bucket {bucket}")
        CHECK(bucket % local_dev == 0,
              f"device_place_batch: bucket {bucket} must be a multiple of "
              f"the {local_dev} local devices (use parts_bucket)")
        padded = np.full(bucket, -1, np.int32)
        padded[: len(ids)] = ids
        to_parts = functools.partial(place_parts, self._mesh, nproc=nproc)
        gids = crossing.place(padded, to_parts)
        if deltas is None:
            return gids
        if isinstance(deltas, jax.Array):   # stays in HBM: no crossing
            d = deltas.reshape(len(ids), self.num_cols).astype(self.dtype)
            if len(ids) < bucket:
                d = jnp.pad(d, ((0, bucket - len(ids)), (0, 0)))
            return gids, to_parts(d)
        d = np.zeros((bucket, self.num_cols), self.dtype)
        d[: len(ids)] = np.asarray(deltas, self.dtype).reshape(
            len(ids), self.num_cols)
        return gids, crossing.place(d, to_parts)

    def device_fetch_rows(self, row_ids) -> jax.Array:
        """Rows for ``row_ids`` as a DEVICE array (never leaves HBM),
        exactly ``len(row_ids)`` of them. A consecutive run on one shard
        is read by a slice; no ids are copied (``_fetch_run``). Any other
        set is padded to its bucket on the host, copied once and gathered.
        A batch that IS its bucket is returned as it is (no cut program).
        Multi-process: collective; one merged SPMD gather round."""
        nproc = multihost.world_size()
        with self._verb_span("server.table.device_fetch"):
            with ttrace.span("server.table.device_fetch.prepare",
                             cat="server"):
                ids = np.asarray(row_ids, np.int32).ravel()
                self._check_ids(ids)
                tmetrics.counter("table.device_fetch.rows").inc(len(ids))
                tmetrics.counter("table.device_fetch.bytes").inc(
                    len(ids) * self.num_cols * self.dtype.itemsize)
                if nproc > 1:
                    gids = self.device_place_batch(ids)
                elif (run := self._fetch_run(ids)) is None:
                    padded = self._pad_ids(ids)
            with ttrace.span("server.table.device_fetch.dispatch",
                             cat="server"):
                if nproc > 1:
                    bucket = gids.shape[0] // nproc
                    with crossing.call("_gather_rows_parts"):
                        rows = self._gather_rows_parts_j(
                            self.state["data"], self.state["aux"], gids)
                    # rows is fully replicated: slice THIS process's range
                    # out of an addressable single-device copy — a
                    # per-process-divergent slice of the global array
                    # would claim replicated contents it doesn't have
                    start = multihost.world_rank() * bucket
                    with crossing.call("slice"):
                        return rows.addressable_data(0)[
                            start: start + len(ids)]
                if run is not None:
                    with crossing.call("_slice_rows"):
                        rows = self._slice_rows(self.state["data"], **run)
                    return _cut_rows(rows, len(ids))
                device_ids = self._place_small(padded)
                with crossing.call("_gather_rows"):
                    rows = self._gather_rows(self.state["data"],
                                             self.state["aux"], device_ids)
                return _cut_rows(rows, len(ids))

    def device_apply_rows(self, row_ids, deltas,
                          option: Optional[AddOption] = None) -> None:
        """Apply a (device or host) delta batch to ``row_ids`` in place —
        same validation and duplicate pre-combining as ProcessAdd: repeated
        ids sum before the updater runs. A device-resident delta whose ids
        repeat is combined ON the device by the host's inverse map (read
        off a rank scratch, ``_inverse_of_repeats``: int32, ``num_rows``
        long, made at the first such apply; the payload never leaves HBM);
        a host numpy delta keeps the host combine. A distinct id set takes
        neither. What the dispatch hands the device is one copy and one
        call: ids and inverse map are padded on the host and copied once,
        the option's scalars are the table's kept ones (``_device_opt``),
        and a delta whose length is its bucket goes to the row program as
        it is — no pad program runs; a shorter one is padded on the device
        (``_pad_rows``). The delta is never donated: the caller's array
        stays readable. Multi-process: collective; batches merge on device."""
        nproc = multihost.world_size()
        with self._verb_span("server.table.device_apply"):
            with ttrace.span("server.table.device_apply.prepare",
                             cat="server"):
                ids = np.asarray(row_ids, np.int32).ravel()
                self._check_ids(ids)
                positions = unique = len(ids)
                on_device = isinstance(deltas, jax.Array)
                inv = None      # set: combine on the device in the dispatch
                if nproc > 1:
                    gids, gdeltas = self.device_place_batch(ids, deltas)
                else:
                    if not on_device:
                        deltas = np.asarray(deltas, self.dtype).reshape(
                            positions, self.num_cols)
                    with ttrace.child(".unique"):
                        uniq = np.unique(ids)
                    unique = len(uniq)
                    if unique != positions:
                        # duplicates must pre-combine (scatter order is
                        # undefined — module docstring)
                        with ttrace.span(
                                "server.table.device_apply.combine",
                                cat="server"):
                            if on_device:
                                # the merged-Add program: one segment-sum
                                # by the host's inverse map (pad lanes:
                                # segment -1, which segment_sum drops),
                                # then the row update at the unique
                                # count's POWER-OF-TWO bucket (as
                                # ProcessAddRun: the count varies from
                                # batch to batch, the ladder's
                                # quarter-octave rungs would each be a
                                # compile)
                                inv = self._pad_ids(
                                    self._inverse_of_repeats(uniq, ids))
                                padded = self._pad_ids(uniq, max(
                                    8, 1 << (unique - 1).bit_length()))
                            else:
                                ids, deltas = self._combine_duplicates(
                                    ids, deltas)
                    if inv is None:
                        padded = self._pad_ids(ids)
                tmetrics.counter("table.device_apply.rows").inc(positions)
                tmetrics.counter("table.device_apply.unique_rows").inc(unique)
                # the device chooses the dense run (ops/rows.py _dense_run);
                # the host counts the batches it will accept, by the test
                # that chooses a fetch's slice (``_is_run``)
                dense_runs = tmetrics.counter("table.device_apply.dense_runs")
                if (nproc == 1 and unique == positions
                        and self._is_run(ids, len(padded))):
                    dense_runs.inc()
                tmetrics.counter("table.device_apply.bytes").inc(
                    positions * self.num_cols * self.dtype.itemsize)
                # bytes of delta copied to the host to combine repeats:
                # none since the device combine; registered (at 0) so that
                # a path which brings the copy back has a counter to step
                # (``crossing.take(..., also=`` this name ``)``)
                tmetrics.counter("table.device_apply.d2h_bytes")
                self._count_apply_write(len(gids if nproc > 1 else padded))
            with ttrace.span("server.table.device_apply.dispatch",
                             cat="server"):
                opt = self._device_opt(option)
                if nproc > 1:
                    with crossing.call("_update_rows_parts"):
                        self.state = self._update_rows_parts_j(
                            self.state, gids, gdeltas, opt)
                    return
                if not on_device:
                    # a host delta is shipped at its exact size
                    deltas = crossing.place(deltas)
                else:
                    # each of the two is a program only where it changes
                    # something
                    if deltas.shape != (positions, self.num_cols):
                        with crossing.call("reshape"):
                            deltas = deltas.reshape(positions, self.num_cols)
                    if deltas.dtype != self.dtype:
                        with crossing.call("astype"):
                            deltas = deltas.astype(self.dtype)
                if inv is not None:
                    padded, inv = self._place_small((padded, inv))
                    deltas = _pad_rows(deltas, inv.shape[0])
                    with crossing.call("_merged_add_rows"):
                        self.state = self._merged_add_rows(
                            self.state, padded, deltas, inv, opt)
                    return
                padded = self._place_small(padded)
                deltas = _pad_rows(deltas, len(padded))
                with crossing.call("_update_rows"):
                    self.state = self._update_rows(self.state, padded,
                                                   deltas, opt)

    def _count_apply_write(self, bucket: int) -> None:
        """One apply verb under the write its row program takes on this
        table's shard at ``bucket`` id lanes (``ops.row_write``, static
        shapes only): ``table.device_apply.pallas_verbs``, ``.xla_verbs``
        or ``.small_table_verbs``. The name is kept a bucket, as the jit
        cache keeps the program: a verb pays one dictionary lookup."""
        names = self.__dict__.setdefault("_apply_write_counter", {})
        name = names.get(bucket)
        if name is None:
            name = names[bucket] = "table.device_apply.%s_verbs" % (
                ops.row_write(self.shard_rows, self.store_cols, self.dtype,
                              bucket))
        tmetrics.counter(name).inc()

    def _inverse_of_repeats(self, uniq: np.ndarray,
                            ids: np.ndarray) -> np.ndarray:
        """``np.searchsorted(uniq, ids)`` for validated ``ids`` whose sorted
        distinct values are ``uniq``, element for element, with no search:
        each distinct id's rank is written into an int32 scratch at the id
        and read back at every position, two passes over the ids where the
        binary searches took 30 ms for 204,800 ids on the chip's host. The
        scratch is the table's, ``num_rows`` long (ids are global row ids
        whatever the sharding; 4 bytes a row of HOST memory, its pages
        touched only where ids fall), made at the first apply whose ids
        repeat and never cleared: an entry outside ``uniq`` is never read.
        The caller owns the table during a verb, so it needs no lock.
        Steps ``table.device_apply.combined_verbs``: one an apply that
        takes the device combine."""
        tmetrics.counter("table.device_apply.combined_verbs").inc()
        rank = getattr(self, "_rank_scratch", None)
        if rank is None:    # a table whose applies are all distinct has none
            rank = self._rank_scratch = np.empty(self.num_rows, np.int32)
        rank[uniq] = np.arange(len(uniq), dtype=np.int32)
        return rank.take(ids)

    def _is_run(self, ids: np.ndarray, bucket: int) -> bool:
        """Whether validated ``ids`` are a dense run of ``bucket`` lanes:
        one shard; ids strictly consecutive, the ends first (O(1): a set
        with repeats or gaps pays nothing more), then one comparison over
        the ids (40 us for 163,840, which does not show in a step); and
        the bucket-long slice inside the live rows (``ops/rows.py``
        ``_dense_run``'s own condition: a ``dynamic_slice`` that reached
        the trash row would clamp). Decided here, on the host, because the
        host holds the ids before anything is copied: no ``cond`` on the
        device, which over a live table copies it."""
        n = len(ids)
        return (self.num_servers == 1 and n > 0
                and int(ids[-1]) - int(ids[0]) == n - 1
                and int(ids[0]) + bucket <= self.block_rows
                and np.array_equal(ids, np.arange(ids[0], ids[0] + n,
                                                  dtype=np.int32)))

    #: the dense read (``ops.slice_rows``) as a program; not donating: the
    #: table is live. ``bucket`` and ``num_cols`` are its static shape,
    #: ``count=None`` a run that is its bucket.
    _slice_rows = staticmethod(jax.jit(
        jax.named_scope("table.slice_rows")(ops.slice_rows),
        static_argnames=("bucket", "num_cols")))

    def _fetch_run(self, ids: np.ndarray) -> Optional[dict]:
        """``device_fetch_rows``' choice of program in a one-process world:
        the small operands of ``_slice_rows`` when ``ids`` are a run
        (``_is_run`` at their bucket), else None and the verb gathers.
        For a run no ids are padded or copied: ``start`` and ``count`` go
        in as scalars with the call. An updater with an ``access`` hook
        keeps the gather, whose program applies the hook. Steps
        ``table.device_fetch.dense_runs`` (registered at 0 otherwise)."""
        from multiverso_tpu.updaters.base import Updater
        dense_runs = tmetrics.counter("table.device_fetch.dense_runs")
        bucket = next_bucket(len(ids))
        if (not self._is_run(ids, bucket)
                or type(self.updater).access is not Updater.access):
            return None
        dense_runs.inc()
        return {"start": np.int32(ids[0]), "bucket": bucket,
                "count": None if len(ids) == bucket else np.int32(len(ids)),
                "num_cols": self.num_cols}

    def raw(self) -> np.ndarray:
        """Logical-view snapshot (host numpy)."""
        return self._from_storage(self._zoo.mesh_ctx.fetch(self.state["data"]))

    # -- serving-plane export (tables/base.py contract) ---------------------

    def serving_export(self):
        """Immutable row snapshot for the serving plane. Residence per
        ``-mv_serving_residence``:

        * device (single-process, aux-free) -> ONE on-device jnp.copy of
          the padded storage — a bare reference would dangle after the
          next donated update (donate_argnums) — served through the
          table's own jit'd row gather (ops.rows/pallas_rows), so only
          requested rows ever cross to the host;
        * otherwise -> the logical host materialization ``_full_logical``
          (applies access(); in multi-process worlds its replicated read
          is a matched collective because the Publish dispatch runs at a
          lockstep stream position — and host residence is MANDATORY
          there, since serving threads must never issue device
          collectives that could interleave with engine ones)."""
        from multiverso_tpu.serving import snapshot as ssnap
        mode = ssnap.residence_mode()
        device_legal = (multihost.world_size() <= 1
                        and not jax.tree.leaves(self.state["aux"]))
        want_device = mode == "device" or (
            mode == "auto" and jax.default_backend() != "cpu")
        if want_device and device_legal:
            return ssnap.MatrixSnapshot.device(
                jnp.copy(self.state["data"]), self.state["aux"],
                self._gather_rows, self._device_ids, self.num_rows,
                self.num_cols)
        return ssnap.MatrixSnapshot.host(self._full_logical())

    # -- aux (updater state) <-> logical layout, for the checkpoint driver --

    # The checkpoint's form is independent of the mesh and of how a shard
    # stacks its workers: shared state (rows, cols), per-worker state
    # (workers, rows, cols) — what every earlier layout wrote too.

    def aux_to_logical(self, keypath: str, leaf) -> np.ndarray:
        """A stored state leaf -> its logical form (interleaving, trash
        rows and column pad stripped)."""
        host = self._zoo.mesh_ctx.fetch(leaf)
        if not self.updater.is_per_worker(keypath):
            return self._from_storage(host)
        return np.stack([self._from_storage(h) for h in unstack_workers(
            host, self._zoo.num_workers, self.num_servers)])

    def aux_from_logical(self, keypath: str, arr: np.ndarray) -> np.ndarray:
        if not self.updater.is_per_worker(keypath):
            return self._to_storage(arr)
        return stack_workers(np.stack([self._to_storage(a) for a in arr]),
                             self.num_servers)

    # -- checkpoint (reference matrix_table.cpp:457-465) --------------------

    def Store(self, stream) -> None:
        stream.WriteInt(self.num_rows)
        stream.WriteInt(self.num_cols)
        stream.Write(self.raw().tobytes())

    def Load(self, stream) -> None:
        rows, cols = stream.ReadInt(), stream.ReadInt()
        CHECK(rows == self.num_rows and cols == self.num_cols,
              "checkpoint shape mismatch")
        raw = stream.Read(rows * cols * self.dtype.itemsize)
        values = np.frombuffer(raw, self.dtype).reshape(rows, cols)
        ctx = self._zoo.mesh_ctx
        self.state = dict(self.state)
        self.state["data"] = ctx.place(self._to_storage(values),
                                       self._sharding)

    # -- device plane, pooled: a bag's rows summed where they live ----------
    # Two thin methods below every other of the class (no line above
    # moves); the programs and the host's handling of jagged bags are
    # tables/pooled.py's, imported and built by a table's first pooled verb.

    def device_fetch_pooled(self, row_ids, lengths, *,
                            padded: bool = False) -> jax.Array:
        """The sums of bags of rows as a DEVICE array (never leaves HBM),
        ``(len(lengths), num_cols)`` float32. ``row_ids`` holds the
        positions bag after bag, ``lengths[b]`` how many belong to bag
        ``b`` (torch's ``EmbeddingBag`` offsets as lengths, torchrec's
        ``KeyedJaggedTensor``; ``sum(lengths) == len(row_ids)``, checked).
        Row ``b`` is the float32 sum of the table's rows at bag ``b``'s
        positions, a repeated id counted as often as it stands; an empty
        bag gives a row of zeros. One copy in (the ids and the
        position-to-bag map, padded on the host) and ONE program: the
        gather ends in a segment sum by bag, and no row a position exists
        outside it. Positions pad to the row verbs' id ladder and bags to
        a ladder of their own (``pooled.program_key``); like
        ``device_fetch_rows`` the result is cut to ``len(lengths)`` rows
        by a slice program, one a distinct count, unless the count is its
        rung. **``padded=True`` returns the bags' rung as it is**
        (``pooled.bag_bucket(len(lengths))`` rows, those past
        ``len(lengths)`` zero) and runs no cut: for a caller whose bag
        count changes from verb to verb, as a row-sharded server's does;
        ``device_apply_pooled`` takes the gradients back at either length.
        A table over several devices of one process pools after the
        ``shard_map`` gather, as ``device_fetch_rows`` gathers.
        Multi-process: REFUSED by a ``CHECK`` (no silent wrong answer)."""
        from multiverso_tpu.tables import pooled
        return pooled.fetch_pooled(self, row_ids, lengths, padded)

    def device_apply_pooled(self, row_ids, lengths, bag_deltas,
                            option: Optional[AddOption] = None) -> None:
        """Apply one (device or host) gradient row a BAG to the rows the
        bags name, in place: every position of bag ``b`` carries
        ``bag_deltas[b]`` (the backward of a sum). The guarantee is
        ``device_apply_rows``' own: the deltas of all positions that name
        one row, within a bag and across bags, are summed before the
        updater runs once on that row; rows no position names keep their
        values bit for bit; the delta never leaves the device and is not
        donated. In its effect on rows and updater state it is
        ``device_apply_rows(row_ids, bag_deltas[bag of each position],
        option)``, but the delta a position exists inside the program
        only: spread, combine and row update are ONE program (a gather by
        the bag map feeding the segment sum by the host's inverse map,
        then the row update at the distinct count's power-of-two bucket,
        as ``_merged_add_rows``), keyed by ``pooled.program_key``.
        ``bag_deltas`` is ``(len(lengths), num_cols)`` or the bags' rung's
        rows (what a ``padded`` fetch returned; rows past ``len(lengths)``
        are never read). Several devices of one process: through the
        ``shard_map`` row update. Multi-process: REFUSED by a ``CHECK``."""
        from multiverso_tpu.tables import pooled
        pooled.apply_pooled(self, row_ids, lengths, bag_deltas, option)


class MatrixWorkerTable(WorkerTable):
    """Worker half (reference matrix_table.h:26-77)."""

    telemetry_label = "matrix"

    def __init__(self, num_rows: int, num_cols: int, dtype=np.float32,
                 compress: Optional[str] = None):
        super().__init__()
        self.num_rows = num_rows
        self.num_cols = num_cols
        self.dtype = np.dtype(dtype)
        self._compress = compress
        self._onebit = None
        if compress == "1bit":
            from multiverso_tpu.utils.quantization import RowOneBitsFilter
            self._onebit = RowOneBitsFilter(num_rows, num_cols)
            self._onebit_lock = threading.Lock()

    def _compressed_payload(self, ids: np.ndarray,
                            deltas: np.ndarray) -> Optional[dict]:
        """Compress a row-set delta batch for the wire, or None when the
        dense payload wins (sparse filter's >50%-zeros rule) / the mode
        is off. Duplicate ids pre-combine here — compression and the
        1-bit residual are per unique row."""
        if self._compress is None:
            return None
        ids = np.asarray(ids, np.int32).ravel()
        if (ids.size == 0 or int(ids.min()) < 0
                or int(ids.max()) >= self.num_rows):
            # invalid ids take the DENSE path: the server's _check_ids
            # produces the proper caller-side error with NO side effects
            # (compressing first would corrupt the 1bit residual)
            return None
        deltas = np.asarray(deltas, self.dtype).reshape(len(ids),
                                                        self.num_cols)
        ids, deltas = _combine_duplicate_rows(ids, deltas, self.num_cols,
                                              self.dtype)
        if self._compress == "sparse":
            from multiverso_tpu.utils.quantization import SparseFilter
            is_sparse, idx, val = SparseFilter().compress(deltas)
            if not is_sparse:
                return None   # dense fallback: the normal payload
            return {"kind": "sparse", "row_ids": ids,
                    "idx": idx, "val": val.astype(self.dtype)}
        with self._onebit_lock:
            packed, pos, neg = self._onebit.compress(
                ids, deltas, next_bucket(len(ids)))
        return {"kind": "1bit", "row_ids": ids, "packed": packed,
                "pos": pos, "neg": neg}

    # -- sync verbs ---------------------------------------------------------

    def Get(self, option: Optional[GetOption] = None) -> np.ndarray:
        """Whole-table get (reference matrix_table.h:30-36). On every
        backend the reply may be a read-only view of the table's host
        copy (it is on one shard): copy it before writing into it."""
        return self.Wait(self.GetAsync({"row_ids": None}, option))

    def GetRows(self, row_ids, option: Optional[GetOption] = None) -> np.ndarray:
        """Row-set get; rows returned in the requested order
        (reference ProcessReplyGet scatter, matrix_table.cpp:317). On
        every backend the reply is a read-only view of the host copy of
        the gather's bucket: copy it before writing into it."""
        ids = np.asarray(row_ids, np.int32)
        return self.Wait(self.GetAsync({"row_ids": ids}, option))

    def Add(self, delta: np.ndarray, option: Optional[AddOption] = None) -> None:
        self.Wait(self.AddAsync(
            {"row_ids": None, "values": np.asarray(delta, self.dtype)}, option))

    def AddRows(self, row_ids, deltas: np.ndarray,
                option: Optional[AddOption] = None) -> None:
        ids = np.asarray(row_ids, np.int32)
        comp = self._compressed_payload(ids, deltas)
        if comp is not None:
            self.Wait(self.AddAsync({"compressed": comp}, option))
            return
        self.Wait(self.AddAsync(
            {"row_ids": ids, "values": np.asarray(deltas, self.dtype)}, option))

    # -- async verbs --------------------------------------------------------

    def GetAsyncHandle(self, row_ids=None, option=None) -> int:
        ids = None if row_ids is None else np.asarray(row_ids, np.int32)
        return self.GetAsync({"row_ids": ids}, option)

    def AddAsyncHandle(self, deltas, row_ids=None, option=None) -> int:
        ids = None if row_ids is None else np.asarray(row_ids, np.int32)
        if ids is not None:
            comp = self._compressed_payload(ids, deltas)
            if comp is not None:
                return self.AddAsync({"compressed": comp}, option)
        return self.AddAsync(
            {"row_ids": ids, "values": np.asarray(deltas, self.dtype)}, option)

    def AddFireForget(self, deltas, row_ids=None, option=None) -> None:
        """Untracked async push (no Waiter/result bookkeeping)."""
        ids = None if row_ids is None else np.asarray(row_ids, np.int32)
        if ids is not None:
            comp = self._compressed_payload(ids, deltas)
            if comp is not None:
                self.AddAsync({"compressed": comp}, option, track=False)
                return
        self.AddAsync(
            {"row_ids": ids, "values": np.asarray(deltas, self.dtype)},
            option, track=False)

    # -- write combining (round 7; tables/base.py contract) -----------------

    def _combinable_fire_forget(self, payload) -> bool:
        """Row-set Adds with a plain dense delta combine: concatenated
        (ids, deltas) batches apply as ONE Add whose duplicate-row
        pre-combine (server _combine_duplicates, np.add.at) sums in
        concatenation = submission order — exactly the engine's own
        merged-run semantics for a fire-and-forget burst. Whole-table
        payloads decline (combining would SUM them, sound only for
        linear updaters the worker half can't see). COMPRESSED TABLES
        decline entirely — not just compressed payloads: the sparse
        filter's compress-or-dense decision is data-dependent PER RANK
        (>50%-zeros rule), so buffering only the dense fallbacks would
        make the combining decision itself data-dependent and diverge
        the SPMD verb streams across ranks. ``self._compress`` is
        creation-time rank-agreed config, so gating on it keeps the
        stream lockstep."""
        return (self._compress is None
                and payload.get("row_ids") is not None
                and payload.get("compressed") is None
                and isinstance(payload.get("values"), np.ndarray))

    def _combine_fire_forget(self, payloads) -> dict:
        ids = np.concatenate([np.asarray(p["row_ids"], np.int32).ravel()
                              for p in payloads])
        vals = np.concatenate(
            [np.asarray(p["values"], self.dtype).reshape(-1, self.num_cols)
             for p in payloads])
        return {"row_ids": ids, "values": vals}

    def server(self) -> MatrixServerTable:
        """The co-located server half — device-plane access (TPU workers
        share the mesh with the store, so the 'network' is ICI)."""
        return self._zoo.server_tables[self.table_id]

    # -- pure partition math (reference matrix_table.cpp:235-296) -----------

    def Partition(self, row_ids, num_servers: Optional[int] = None) -> Dict[int, list]:
        """Bucket row ids by owning server — unit-testable pure function.

        Uses the storage ownership actually in effect (ceil blocks, see
        parallel/mesh.py); matches the reference floor math whenever
        num_servers divides num_rows. Vectorized (round 7): the old
        per-row python loop over storage_partition_server cost ~1us/row
        — a 100k-id batch paid 100ms of interpreter time for pure
        integer math."""
        if num_servers is None:
            num_servers = self._zoo.num_servers
        ids = np.asarray(row_ids, np.int64).ravel()
        block = ceil_block_rows(self.num_rows, num_servers)
        owners = np.minimum(ids // block, num_servers - 1)
        out: Dict[int, list] = {}
        for s in np.unique(owners):
            out[int(s)] = [int(r) for r in ids[owners == s]]
        return out
