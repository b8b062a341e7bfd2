"""SparseMatrixTable — MatrixTable + per-worker row freshness tracking.

Behavioral equivalent of reference
include/multiverso/table/sparse_matrix_table.h +
src/table/sparse_matrix_table.cpp: the server keeps an ``up_to_date`` bit
per (worker, row). An Add from worker w marks the touched rows stale for
every *other* worker (UpdateAddState, sparse_matrix_table.cpp:200-223); a Get
from worker w returns only the rows stale for w and re-marks them fresh,
falling back to row 0 when nothing changed (UpdateGetState,
sparse_matrix_table.cpp:226-259); ``worker_id == -1`` fetches everything.
``tables/sparse_reference.py`` is that protocol in plain numpy, bit by
bit; this module must agree with it on every interleaving.
The wire-compression (SparseFilter) of the reference's Add/Get payloads
(sparse_matrix_table.cpp:262-266) is host-side delta compression here
(utils/quantization.py) applied by apps before AddRows.

TPU design (``docs/DESIGN.md`` 3.1): the freshness state is kept as
what it is in use, a small set of stale rows a worker, not as a
``(workers, rows)`` bit matrix: each global worker owns a ``_DirtyRows``,
the ids that Adds of other workers named since its last Get, as the id
arrays those Adds brought. It lives on the host beside the engine; only
row data lives in HBM. An Add appends its (copied) id array to every other
worker's set, one list append a worker. A Get without ids concatenates
what was appended, empties the set and hands the ids to the device as
they are: one program sorts them, drops repeats and gathers, while the
host sorts its own copy for the reply (``read_stale``). A Get with ids
folds the set to one sorted array, looks the ids up and takes the hits
out. No verb does work in proportion to the table's rows unless it names
that many (a bit matrix cost a scan of ``num_rows`` bits a Get: 2.9 ms at
9,000,000 rows, 42 % of the wall of the cell ``mt_sparse_rounds`` when it
was first measured). A Get returns its rows in ascending id order, each
once.

The number of stale rows differs from Get to Get, and a slice on the
device would compile a program a count: the reads pad the ids to the
bucket ladder, copy the whole bucket back and cut the pad off on the
host. Above ``READ_ROWS_CAP`` ids they read in pieces padded to the cap,
so the shapes a table can ever compile are ``read_buckets()``, finite and
known before the first verb.

Multi-process design (reference parity: the dirty-row protocol is
inherently multi-worker-multi-node, sparse_matrix_table.cpp:200-259):
the dirty sets are REPLICATED per process and keyed by *global* worker id
``rank * num_workers + local_wid`` — every (process, worker thread) pair
is a distinct physical consumer that must see each update once. Lockstep
holds because every table op is collective (the parent's contract):
Adds/Gets allgather their (worker_id, row_ids) parts, and every process
applies every part's freshness transition in rank order — the same
global event stream a single shared server would see, so the replicas
can never diverge. The data gather itself rides the parent's union
collective (one identical device program everywhere).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from multiverso_tpu.parallel.mesh import next_bucket
from multiverso_tpu.tables import crossing
from multiverso_tpu.tables.matrix_table import (MatrixServerTable,
                                                MatrixTableOption,
                                                MatrixWorkerTable)
from multiverso_tpu.telemetry import metrics as tmetrics
from multiverso_tpu.telemetry import trace as ttrace
from multiverso_tpu.updaters.base import AddOption, GetOption
from multiverso_tpu.utils.log import CHECK

_NO_ROWS = np.zeros(0, np.int32)
#: what the device's dedup puts in the lanes it empties: above every id
_ID_PAD = np.iinfo(np.int32).max


@dataclass
class SparseMatrixTableOption(MatrixTableOption):
    def make_server(self, zoo):
        return SparseMatrixServerTable(self.num_rows, self.num_cols,
                                       self.dtype, zoo, self.updater_type,
                                       self.initializer,
                                       compress=self.compress)

    def make_worker(self, zoo):
        return SparseMatrixWorkerTable(self.num_rows, self.num_cols,
                                       self.dtype, compress=self.compress)


class _DirtyRows:
    """The rows stale for ONE worker: the int32 id arrays that Adds
    appended since the worker last drained them. The set is the union of
    ``chunks``; ``canon`` says they are one sorted array without repeats
    (or none). ``touched`` counts the elements every select has read or
    written, which is what a test holds to the rows marked and not to the
    table's rows."""

    __slots__ = ("chunks", "pending", "canon", "touched")

    def __init__(self):
        self.chunks: List[np.ndarray] = []
        self.pending = 0        # ids in chunks, repeats counted
        self.canon = True
        self.touched = 0

    def mark(self, ids: np.ndarray, limit: int) -> None:
        """``ids`` is never written again by anyone. Past ``limit`` ids
        the chunks hold repeats for sure: fold them, so the set never
        outgrows the table (paid for by the ``limit`` marks before)."""
        self.chunks.append(ids)
        self.pending += ids.size
        self.canon = False
        if self.pending > limit:
            self._canonical()

    def mark_all(self, every_row: np.ndarray) -> None:
        self.chunks, self.pending, self.canon = ([every_row],
                                                 every_row.size, True)

    def _canonical(self) -> np.ndarray:
        if not self.canon:
            cat = (self.chunks[0] if len(self.chunks) == 1
                   else np.concatenate(self.chunks))
            self.touched += cat.size
            rows = np.unique(cat)
            self.chunks, self.pending, self.canon = [rows], rows.size, True
        return self.chunks[0] if self.chunks else _NO_ROWS

    def drain(self) -> np.ndarray:
        """Every stale row, ascending, each once; the set is empty after."""
        rows = self._canonical()
        self.chunks, self.pending = [], 0
        return rows

    def drain_raw(self) -> np.ndarray:
        """Every stale row as the Adds appended them: any order, repeats
        kept, for a reader that sorts and dedups on the device. The set is
        empty after."""
        rows = (_NO_ROWS if not self.chunks else self.chunks[0]
                if len(self.chunks) == 1 else np.concatenate(self.chunks))
        self.touched += rows.size
        self.chunks, self.pending, self.canon = [], 0, True
        return rows

    def take_stale(self, ids: np.ndarray) -> np.ndarray:
        """The stale rows among ``ids``, ascending, each once; they leave
        the set."""
        rows = self._canonical()
        if rows.size == 0:
            return _NO_ROWS
        ask = np.unique(ids)
        at = np.searchsorted(rows, ask)
        at[at == rows.size] = 0
        hit = rows[at] == ask
        self.touched += ids.size
        if not hit.any():
            return _NO_ROWS
        keep = np.ones(rows.size, bool)
        keep[at[hit]] = False
        rest = rows[keep]
        self.touched += rows.size
        self.chunks, self.pending = ([rest] if rest.size else []), rest.size
        return ask[hit]

    def nbytes(self) -> int:
        return sum(c.nbytes for c in list(self.chunks))


class SparseMatrixServerTable(MatrixServerTable):
    #: most ids one gather of ``read_rows`` takes; a larger set is read in
    #: pieces of this size (13 MB of 50-column f32 rows a piece)
    READ_ROWS_CAP = 65536

    def __init__(self, num_rows, num_cols, dtype, zoo, updater_type=None,
                 initializer=None, compress=None):
        super().__init__(num_rows, num_cols, dtype, zoo, updater_type,
                         initializer, compress=compress)
        from multiverso_tpu.parallel import multihost
        self._procs = max(1, multihost.world_size())
        self._rank = multihost.world_rank() if self._procs > 1 else 0
        self._workers_per_proc = zoo.num_workers
        if self._procs > 1:
            # the gwid mapping for EVERY rank is computed from the local
            # flag — mismatched -num_workers would silently diverge the
            # replicated sets, so agreement is checked once at creation
            counts = multihost.host_allgather_objects(zoo.num_workers)
            CHECK(all(c == counts[0] for c in counts),
                  f"-num_workers diverges across processes: {counts}")
        # all fresh at start (reference ctor sets true,
        # sparse_matrix_table.cpp:184-196): one empty set per GLOBAL
        # worker — see module docstring (multi-process design)
        self._dirty = [_DirtyRows()
                       for _ in range(self._procs * zoo.num_workers)]
        gather = self.device_gather_rows

        @jax.named_scope("table.sparse.read_stale")
        def _read_stale(data, aux, ids):
            """ids as Adds appended them (repeats, pad lanes -1) -> the
            rows of the distinct ids in ascending order, then pad rows:
            two sorts of a bucket on the device, so that the gather need
            not wait for the host's. Unstable sorts: equal keys are
            equal, and the stable sort takes four times as long to
            compile."""
            ids = lax.sort(ids, is_stable=False)
            first = jnp.concatenate(
                [jnp.ones(1, bool), ids[1:] != ids[:-1]])
            ids = lax.sort(jnp.where(first & (ids >= 0), ids, _ID_PAD),
                           is_stable=False)
            return gather(data, aux, jnp.where(ids == _ID_PAD, -1, ids))

        self._read_stale = jax.jit(_read_stale)

    @property
    def up_to_date(self) -> np.ndarray:
        """The reference's ``(workers, rows)`` bit matrix, built from the
        dirty sets: for tests and debugging, never on a verb's path (it
        costs what the matrix costs)."""
        bits = np.ones((len(self._dirty), self.num_rows), dtype=bool)
        for w, dirty in enumerate(self._dirty):
            for chunk in list(dirty.chunks):
                bits[w, chunk] = False
        return bits

    @property
    def select_touched(self) -> int:
        """Elements the selects of every Get so far have read or written."""
        return sum(d.touched for d in self._dirty)

    def ledger_bytes(self):
        """Matrix placement plus the per-worker dirty id sets — the
        host-authoritative state the dense family doesn't carry."""
        out = super().ledger_bytes()
        out["host_bytes"] += int(sum(d.nbytes() for d in self._dirty))
        return out

    def _gwid(self, rank: int, worker_id: int) -> Optional[int]:
        """Global worker id, or None for out-of-range/-1 ids — a
        system-level push with no owning worker (reference UpdateAddState
        tolerates these: no keeper, everyone goes stale)."""
        if not 0 <= worker_id < self._workers_per_proc:
            return None
        return rank * self._workers_per_proc + worker_id

    def _mark_stale(self, keeper: Optional[int],
                    row_ids: Optional[np.ndarray]) -> None:
        """reference UpdateAddState (sparse_matrix_table.cpp:200-223):
        mark ``row_ids`` (None = all) stale for every global worker except
        ``keeper`` (the physical worker whose own push this was)."""
        with ttrace.span("server.table.sparse.add.mark", cat="server"):
            if row_ids is None:
                ids = np.arange(self.num_rows, dtype=np.int32)
            else:
                # a copy of its own: the caller may reuse its id array
                # after the Add returns, and every set shares this one
                ids = np.array(row_ids, np.int32).ravel()
            ids.setflags(write=False)
            others = [d for w, d in enumerate(self._dirty) if w != keeper]
            for dirty in others:
                if row_ids is None:
                    dirty.mark_all(ids)
                else:
                    dirty.mark(ids, self.num_rows)
            tmetrics.counter("table.sparse.add.marked").inc(
                ids.size * len(others))

    def _update_get_state(self, gwid: int,
                          row_ids: Optional[np.ndarray]) -> np.ndarray:
        """reference UpdateGetState (sparse_matrix_table.cpp:226-259):
        returns the row ids to ship (ascending, each once) and re-marks
        them fresh. ``gwid`` is a global worker id (or -1 = fetch
        everything)."""
        with ttrace.span("server.table.sparse.get.select", cat="server"):
            if gwid == -1:
                return np.arange(self.num_rows, dtype=np.int32)
            if row_ids is None:
                stale = self._dirty[gwid].drain()
            else:
                ids = np.asarray(row_ids, np.int64).ravel()
                # validate BEFORE touching the sets: a rejected Get must
                # not mark rows fresh (negative ids would silently wrap)
                self._check_ids(ids)
                stale = self._dirty[gwid].take_stale(ids.astype(np.int32))
            if stale.size == 0:
                return self._row_zero()
            tmetrics.counter("table.sparse.get.rows").inc(stale.size)
            return stale

    @staticmethod
    def _row_zero() -> np.ndarray:
        """All fresh -> still ship row 0 (sparse_matrix_table.cpp:255-257)."""
        tmetrics.counter("table.sparse.get.empty").inc()
        return np.zeros(1, dtype=np.int32)

    def _allgather_parts(self, part):
        """Every process's (worker_id, row_ids) of this collective op, in
        rank order — identical on every process (lockstep transitions)."""
        if self._procs <= 1:
            return [part]
        from multiverso_tpu.parallel import multihost
        return multihost.host_allgather_objects_capped(part,
                                                       "sparse_parts")

    def _note_add_parts(self, option: AddOption, parts) -> None:
        """Parent hook: fires after the collective Add applied, with every
        rank's id set (already allgathered by the parent's merge — no
        second collective here). The parent's merge CHECKs the AddOption
        (worker_id included) agrees across processes, so one collective
        Add is attributed to the same LOCAL worker id everywhere; the
        per-rank parts still map to distinct GLOBAL keepers (rank*W + wid)
        and each keeper stays fresh only for the rows its own process
        pushed (a rejected add never reaches this hook, so the sets can't
        desynchronize)."""
        # the parent hook carries the replica-plane publish journal
        # (round 17) — the dirty sets below are the TRAINING-side
        # delta machinery, the journal the publish-side one
        super()._note_add_parts(option, parts)
        for rank, part_ids in enumerate(parts):
            self._mark_stale(self._gwid(rank, option.worker_id), part_ids)

    # -- the read behind a Get ----------------------------------------------

    @classmethod
    def read_buckets(cls) -> Tuple[int, ...]:
        """Every id-vector length ``read_rows`` and ``read_stale`` can hand
        their programs: the rungs of the bucket ladder up to
        ``READ_ROWS_CAP``. A caller that wants no compile inside a timed
        stretch reads, before it, a set of each size its Gets can meet
        (``next_bucket`` of the size is the rung)."""
        out, b = [], 1
        while b <= cls.READ_ROWS_CAP:
            b = next_bucket(b)
            out.append(b)
            b += 1
        return tuple(out)

    def read_rows(self, ids: np.ndarray) -> np.ndarray:
        """``(len(ids), num_cols)`` host rows for validated int32 ``ids``;
        no freshness state is read or written. The ids are padded to
        their bucket, the whole bucket comes back in one copy and the pad
        is cut off on the host. More than ``READ_ROWS_CAP`` ids are read
        in pieces, each padded to the cap: one more shape, whatever the
        count."""
        data, aux = self.state["data"], self.state["aux"]
        fetch = self._zoo.mesh_ctx.fetch
        cap = self.READ_ROWS_CAP
        if len(ids) <= cap:
            device_ids = self._device_ids(ids)
            with crossing.call("_gather_rows"):
                rows = self._gather_rows(data, aux, device_ids)
            return crossing.take(rows, fetch)[: len(ids)]
        # dispatch every piece, then copy back: the copies overlap
        pieces = []
        for at in range(0, len(ids), cap):
            piece = ids[at: at + cap]
            device_ids = self._place_small(self._pad_ids(piece, cap))
            with crossing.call("_gather_rows"):
                pieces.append((len(piece),
                               self._gather_rows(data, aux, device_ids)))
        return np.concatenate([crossing.take(rows, fetch)[:n]
                               for n, rows in pieces])

    def read_stale(self, raw: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """``raw``: validated int32 ids, in any order, repeats allowed,
        at least one -> (the distinct ids ascending, their rows), both on
        the host. The device gets ``raw`` as it is, at the bucket of its
        length, and sorts, dedups and gathers in one program; the host
        sorts its own copy (``np.unique``) while that runs and the rows
        come back, so the sort is on nobody's critical path. Past
        ``READ_ROWS_CAP`` ids the host sorts first and ``read_rows``
        reads."""
        if len(raw) > self.READ_ROWS_CAP:
            ids = np.unique(raw)
            return ids, self.read_rows(ids)
        device_ids = self._device_ids(raw)
        with crossing.call("_read_stale"):
            rows = self._read_stale(self.state["data"], self.state["aux"],
                                    device_ids)
        rows.copy_to_host_async()
        with ttrace.child(".unique"):
            ids = np.unique(raw)
        return ids, crossing.take(rows, self._zoo.mesh_ctx.fetch)[: ids.size]

    def _get_all(self, gwid: int) -> Tuple[np.ndarray, np.ndarray]:
        """A single-process Get without ids by worker ``gwid``
        (UpdateGetState, as ``_update_get_state``): the host hands over
        what Adds appended, unsorted, and ``read_stale`` makes it the
        rows to ship."""
        with ttrace.span("server.table.sparse.get.select", cat="server"):
            raw = self._dirty[gwid].drain_raw()
            empty = raw.size == 0
            if empty:
                raw = self._row_zero()
        with ttrace.span("server.table.sparse.get.read", cat="server"):
            ids, rows = self.read_stale(raw)
            if not empty:
                tmetrics.counter("table.sparse.get.rows").inc(ids.size)
            self._note_row_access(ids)
        return ids, rows

    def ProcessGetAsync(self, option: GetOption = None, row_ids=None):
        # a sparse Get MUTATES freshness state and returns (ids, rows) —
        # the inherited matrix fast path would bypass the dirty protocol
        return None

    def ProcessGet(self, option: GetOption, row_ids=None,
                   _parts=None) -> Tuple[np.ndarray, np.ndarray]:
        """Returns (row_ids, rows) — the server decides which rows move.
        ``_parts``: every rank's (worker_id, ids) when the windowed
        engine already exchanged them (no collective here then)."""
        worker_id = option.worker_id if option is not None else -1
        if self._procs == 1 and row_ids is None:
            gwid = self._gwid(0, worker_id)
            if gwid is not None:
                return self._get_all(gwid)
        ids = None if row_ids is None else np.asarray(row_ids, np.int64)
        out_ids = None
        part_outs = []
        if _parts is None:
            _parts = self._allgather_parts((worker_id, ids))
        for rank, (wid, part_ids) in enumerate(_parts):
            gwid = self._gwid(rank, wid)
            part_out = self._update_get_state(-1 if gwid is None else gwid,
                                              part_ids)
            part_outs.append(part_out)
            if rank == self._rank:
                out_ids = part_out
        with ttrace.span("server.table.sparse.get.read", cat="server"):
            if self._procs == 1:
                self._note_row_access(out_ids)
                return out_ids, self.read_rows(out_ids)
            # every rank's stale set is already known here — hand the
            # parent the precomputed union so the ids don't ride a second
            # collective
            union = np.unique(np.concatenate(part_outs)).astype(np.int32)
            rows = super().ProcessGet(GetOption(worker_id=worker_id),
                                      row_ids=out_ids, _union=union)
        return out_ids, rows

    # -- windowed-engine parts hooks (round 5) ------------------------------
    # Cross-rank MERGED ADD-RUNS (round 6): this table inherits the
    # parent's ProcessAddRunParts / ProcessAddPartsDevice unchanged —
    # the freshness protocol PERMITS the merge. Soundness: the data merge
    # is gated on linear updaters (order-free sums), and the parent fires
    # _note_add_parts once per position in window order AFTER the one
    # merged apply; since the engine serves no Get between a run's Add
    # positions (Gets group into the before/after segments around the
    # run), "merged data + ordered notes" is observationally identical
    # to sequential per-position applies — every (worker, row) staleness
    # transition happens at the same point relative to every Get that
    # can see it, on every rank.

    @staticmethod
    def _decode_parts(parts):
        """Every rank's (worker_id, ids or None) of one Get position."""
        decoded = []
        for q in parts:
            qopt = q.get("option")
            qids = q.get("row_ids")
            decoded.append((qopt.worker_id if qopt is not None else -1,
                            None if qids is None
                            else np.asarray(qids, np.int64)))
        return decoded

    def ProcessGetParts(self, parts, my_rank: int):
        """Run the freshness protocol from the exchanged parts — the
        same every-rank-in-rank-order transitions, no collective."""
        p = parts[my_rank]
        return self.ProcessGet(p.get("option"), row_ids=p.get("row_ids"),
                               _parts=self._decode_parts(parts))

    def ProcessGetWindowParts(self, positions, my_rank: int):
        """Sparse Gets MUTATE the freshness state, so the protocol
        transitions still run strictly in position order — but they are
        pure numpy set ops, and since no Add applies between a
        segment's Get positions (the engine's before/after-run
        grouping), every position reads the SAME row data. Round 7
        therefore BATCHES the data movement: all positions' stale sets
        (numpy-segment work, in order) first, then ONE merged row read
        over their union, sliced per position. The old per-position
        serve paid one gather dispatch each — on a remote accelerator
        one dispatch RTT per Get, the '137x below dense' wall in
        BENCH_r05's sparse_matrix_host_Melem_s."""
        per_pos: list = []    # this rank's out_ids, or Exception
        unions: list = []     # per ok position: all ranks' stale union
        for parts in positions:
            try:
                part_outs = []
                out_ids = None
                for rank, (wid, part_ids) in enumerate(
                        self._decode_parts(parts)):
                    gwid = self._gwid(rank, wid)
                    po = self._update_get_state(
                        -1 if gwid is None else gwid, part_ids)
                    part_outs.append(po)
                    if rank == my_rank:
                        out_ids = po
                per_pos.append(out_ids)
                unions.append(np.concatenate(part_outs))
            except Exception as exc:
                # _update_get_state validates BEFORE touching the sets,
                # so a failed position left no partial transitions behind
                per_pos.append(exc)
        if not unions:
            return per_pos      # every position failed validation
        # one merged read over the cross-position cross-rank union —
        # identical on every rank (computed from exchanged parts), so
        # the gather traces one identical program everywhere
        with ttrace.span("server.table.sparse.get.read", cat="server"):
            union = np.unique(np.concatenate(unions)).astype(np.int32)
            rows_u = self._read_rows_union(union)
        out: list = []
        for o in per_pos:
            if isinstance(o, Exception):
                out.append(o)
            else:
                # fancy indexing copies: each position owns its rows
                out.append((o, rows_u[np.searchsorted(union, o)]))
        return out

    def serving_export(self):
        """Row snapshot via the parent hook. Serving reads are
        VERSION-addressed, not freshness-addressed: they bypass the
        dirty-row protocol entirely (it answers "what changed since
        worker w's last training Get", a training-side delta question; a
        serving caller asks "rows R at version V") and therefore never
        mutate it — a read plane must not perturb the training plane's
        state."""
        return super().serving_export()


class SparseMatrixWorkerTable(MatrixWorkerTable):
    """Worker half: Get returns (row_ids, rows) since the server picks the
    rows (reference sparse ProcessReplyGet fills only returned rows)."""

    telemetry_label = "sparse_matrix"

    def Get(self, option: Optional[GetOption] = None):
        if option is None:
            option = GetOption(worker_id=self._zoo.current_worker_id())
        return self.Wait(self.GetAsync({"row_ids": None}, option))

    def GetRows(self, row_ids, option: Optional[GetOption] = None):
        if option is None:
            option = GetOption(worker_id=self._zoo.current_worker_id())
        ids = np.asarray(row_ids, np.int32)
        return self.Wait(self.GetAsync({"row_ids": ids}, option))
