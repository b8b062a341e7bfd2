"""Backend dispatch for the row gather/scatter/update table ops.

One decision, made here and nowhere else:

* **reads** are XLA's native gather, ``jnp.take(..., mode="clip")``, on
  every backend; a run the HOST finds consecutive is ``slice_rows``;
* **writes** are the Pallas row-DMA kernel ``pallas_scatter_set_rows``
  where ``use_pallas(data, ids)`` says so — a TPU backend, a row shape
  Mosaic compiles (``_pallas_eligible``: a 4-byte dtype at exactly one
  128-lane tile a row) and an id vector within ``SMEM_IDS_BYTES`` — and
  XLA's ``.at[].set`` scatter otherwise;
* a **dense run** (consecutive ids, detected at run time by
  ``_dense_run``) takes one bulk slice -> combine -> update-slice where
  ``_dense_backend_ok()`` (TPU only) and the caller allows it, in every
  verb that consumes the table: ``update_rows`` / ``update_gather_rows``
  (aux-free updaters), ``update_rows_with_state`` (the rows and every
  state leaf of a stateful updater under ONE test: a slice, the update
  and an update-slice a table) and ``scatter_set_rows`` (a write of rows
  made elsewhere, which reads the old rows to keep its pad lanes).

This is the split every benchmark cell runs (``PERF_LEDGER.jsonl``'s
``breakdown`` names ``pallas_scatter_set_rows`` and XLA's ``fusion``
gathers, no other kernel). It was chosen in the rounds before the
ledger (r02-r05, on a v5e, by that time's own microbenchmarks). One
figure survives in a record the repo carries: XLA's gather of random
512-byte rows at about 100 GB/s (``BENCH_r05.json``, the plug-in's
``we_device_bound_note``; not the ledger). The rest — a per-row DMA
gather several times slower than that, XLA's serialising scatter an
order of magnitude under the DMA kernel — is in no record, so no
figure is quoted. What the ledger does hold is the other end: at 2,048
columns XLA scatters 8 KB rows at 120-215 GB/s (``PERF.md`` section 6,
PR 25), and such tables are not eligible for the kernel.

``-use_pallas`` is the hook around that decision, not a second design:
``auto`` (default) as above; ``on`` lets the CPU suite run the one
kernel in interpreter mode through a whole table (it drops the TPU
condition and nothing else, so it selects no path ``auto`` cannot
select on a chip); ``off`` lets an operator take every table off the
kernel.

The table layer pads its storage column dim to ``padded_cols``: tables
of up to 128 four-byte columns are stored as one lane tile a row, which
is what keeps them on the kernel and their rows aligned.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from multiverso_tpu.utils.configure import (MV_DEFINE_string,
                                            cached_str_flag)

#: one constant feeds both the flag registration and the cached
#: accessor's fallback, so the two defaults cannot drift apart
_USE_PALLAS_DEFAULT = "auto"
MV_DEFINE_string("use_pallas", _USE_PALLAS_DEFAULT,
                 "row-op kernels: auto (TPU only) / on / off")
#: use_pallas runs per row-op dispatch (every verb on the apply path) —
#: listener-cached read, not a registry walk per call
_use_pallas_flag = cached_str_flag("use_pallas", _USE_PALLAS_DEFAULT)

LANE = 128
#: The Pallas row kernel takes the id vector as a SCALAR-PREFETCH operand in
#: SMEM (1MB/core on v5e): a 262144-id batch (exactly 1MB of i32) OOM'd
#: SMEM by its 1.1KB of spill slots. Id vectors above this BYTE budget
#: (half of SMEM — headroom for spills/other scalars) route to the XLA
#: path; matrix_table's merge cap uses the same constant so merged
#: windows never outgrow the fast path they were built for.
SMEM_IDS_BYTES = 512 * 1024


def _pallas_eligible(data) -> bool:
    """The row shapes Mosaic compiles the kernels for (jax 0.9.0, libtpu
    0.0.34, v5e): a 4-byte dtype at exactly one 128-lane tile per row.
    Wider multiples of 128 are refused at compile time — the per-row DMA
    with 'Slice shape along dimension 0 must be aligned to tiling (8), but
    is 1', the coalesced one with 'Failed to prove that a tile index in
    dimension 0 is divisible by the tiling (8)' — so those tables take the
    XLA path. tests/test_ops.py compiles every width this admits ahead of
    time against a v5e topology."""
    return data.dtype.itemsize == 4 and data.shape[-1] == LANE


def use_pallas(data=None, ids=None) -> bool:
    """Whether a WRITE of ``rows[ids]`` into ``data`` takes the Pallas
    kernel (module docstring). ``on`` drops the backend condition only:
    the lowering constraints hold in every mode — an ineligible shape
    would be a Mosaic compile error rather than a kernel choice."""
    if ids is not None and ids.shape[0] * 4 > SMEM_IDS_BYTES:
        return False   # id vector would overflow the SMEM prefetch
    mode = _use_pallas_flag()
    if mode == "off":
        return False
    return ((mode == "on" or jax.default_backend() == "tpu")
            and (data is None or _pallas_eligible(data)))


def padded_cols(num_cols: int, itemsize: int = 4) -> int:
    """Storage column count for a logical ``num_cols``: 4-byte dtypes pad
    up to the 128-lane tile. Aligned rows are what the Pallas row-DMA
    kernel requires and what keeps XLA's row ops off ragged 200-byte
    rows. The pad trades HBM capacity for alignment; padded columns hold
    zeros and every updater is identity on a zero delta, so they stay
    zero."""
    if itemsize != 4:
        return num_cols
    return -(-num_cols // LANE) * LANE


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def dedup_rows(ids: jax.Array, deltas: jax.Array):
    """Traced duplicate combine: sum the deltas of equal ids into ONE
    surviving lane; the other duplicate lanes become pad lanes (id -1,
    zero delta). Pad lanes in (-1, zero-delta form) pass through.

    This is the on-device equivalent of the host-side ``np.add.at``
    pre-combine the table layer applies before scatter (scatter-set order
    on duplicates is undefined — matrix_table.py module docstring), with
    identical semantics: duplicates combine by SUM before the updater
    runs. It is what makes merged multi-process device-plane batches
    safe for every updater without a host round-trip.

    Cost: one argsort over the id bucket + a segment-sum over the delta
    payload — O(n log n + n·cols), fully fused into the caller's program.
    """
    n = ids.shape[0]
    order = jnp.argsort(ids)
    sids = jnp.take(ids, order)
    sdeltas = jnp.take(deltas, order, axis=0)
    head = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sids[1:] != sids[:-1]])
    seg = jnp.cumsum(head) - 1          # segment index per sorted lane
    out_deltas = jax.ops.segment_sum(sdeltas, seg, num_segments=n)
    # every lane of a segment writes the same id value, so the scatter's
    # undefined duplicate order is harmless; unused segments stay -1 (pad)
    out_ids = jnp.full((n,), -1, ids.dtype).at[seg].set(sids)
    return out_ids, out_deltas


def _dense_backend_ok() -> bool:
    """The dense-run lax.cond is a TPU-only optimization: on the CPU
    backend XLA fails to alias the donated table through a conditional
    whose branches read-modify-write it, so every call copies the whole
    table. On the chip it aliases (``lm_vocab_steps`` writes a whole
    table's rows this way: ``dynamic_update_slice.1``, ``PERF.md``
    section 5)."""
    return jax.default_backend() == "tpu"


def _dense_run(ids: jax.Array, n_rows: int):
    """Traced detector for the DENSE fast path: the non-trash lanes are a
    PREFIX of the lane vector holding strictly consecutive row ids, and
    the bucket-sized slice [start, start+bucket) fits inside the live
    rows (never touches the trash row, so dynamic_slice cannot clamp).
    Returns (ok, start, count).

    Lead-trash batches (a shard seeing the middle of a cross-shard run)
    and interior trash (dedup_rows output) route to the general path on
    purpose: the prefix form needs NO lane rolls — the slice lanes line
    up with the batch lanes 1:1 (rolls plus a read-back slice defeated
    XLA's in-place aliasing of the table buffer, turning every round
    into a whole-table copy)."""
    trash = n_rows - 1
    bucket = ids.shape[0]
    mine = ids != trash
    count = jnp.sum(mine)
    lane = jnp.arange(bucket)
    start = ids[0]
    ok = (jnp.all(mine == (lane < count))
          & jnp.all(jnp.where(mine, ids == start + lane, True))
          & (count > 0) & (start + bucket <= trash))
    return ok, start, count


def gather_rows(data: jax.Array, ids: jax.Array) -> jax.Array:
    """rows[i] = data[ids[i]]; all ids must be in range (caller maps
    out-of-shard lanes to the trash row). Trash/pad lanes may return
    ARBITRARY row content — every caller masks or trash-routes them.

    Reads ride XLA's native gather on every backend (``mode='clip'``:
    the jnp default 'fill' adds an out-of-bounds select the in-range
    contract makes needless).

    NO dense-run cond here, deliberately: a lax.cond over a LIVE
    (non-donated) table defeats XLA's buffer aliasing — each branch gets
    an operand copy of the whole table. The cond lives only in the verbs
    that consume/donate the table (scatter_set_rows, update_rows,
    update_gather_rows, update_rows_with_state), where the in-place
    chain survives it. The dense READ is ``slice_rows``, a program of
    its own that the host chooses from the ids it holds."""
    return jnp.take(data, ids, axis=0, mode="clip")


def _set_rows(data, ids, rows, pallas_write: bool):
    """The general write: the Pallas row-DMA kernel or XLA's scatter."""
    if pallas_write:
        from multiverso_tpu.ops.pallas_rows import pallas_scatter_set_rows
        return pallas_scatter_set_rows(data, ids, rows,
                                       interpret=_interpret())
    return data.at[ids].set(rows)


def scatter_set_rows(data: jax.Array, ids: jax.Array,
                     rows: jax.Array, *, dense: bool = True) -> jax.Array:
    """data[ids[i]] = rows[i]; duplicates only on the trash row.

    Writes are the mirror image of reads on TPU: XLA's scatter
    serialises over one-tile rows, so they keep the Pallas row-DMA
    kernel wherever ``use_pallas`` admits it. A runtime-detected dense
    run takes the bulk slice-merge-update path instead."""
    pallas_write = use_pallas(data, ids)

    def general(_):
        return _set_rows(data, ids, rows, pallas_write)

    if (not dense or not _dense_backend_ok()
            or ids.shape[0] >= data.shape[0]):
        return general(None)   # static guards (see gather_rows)
    ok, start, count = _dense_run(ids, data.shape[0])
    bucket = ids.shape[0]

    def dense_fn(_):
        # bulk RMW: pad lanes must keep OLD rows (a blind bucket write
        # would clobber the live rows after the run's end)
        old = jax.lax.dynamic_slice(data, (start, 0),
                                    (bucket, data.shape[1]))
        keep = (jnp.arange(bucket) < count)[:, None]
        return jax.lax.dynamic_update_slice(
            data, jnp.where(keep, rows, old), (start, 0))

    return jax.lax.cond(ok, dense_fn, general, None)


def update_rows(data: jax.Array, ids: jax.Array, deltas: jax.Array,
                combine, *, dense: bool = True) -> jax.Array:
    """data[ids[i]] = combine(data[ids[i]], deltas[i]) — the server-side
    Add for aux-free elementwise updaters. ``combine`` must be a
    jax-traceable elementwise fn with combine(rows, 0) == rows (pad and
    foreign lanes rely on it) and be identity-stable (one object per
    table) so the jit cache holds.

    One implementation with ``update_gather_rows``: XLA's gather for the
    read half, combine fused elementwise, ``_set_rows`` for the write
    half; a runtime-detected dense run instead does ONE bulk
    dynamic_slice -> combine -> dynamic_update_slice. The dropped rows
    output is an intermediate both branches compute anyway."""
    return update_gather_rows(data, ids, deltas, combine, dense=dense)[0]


def update_gather_rows(data: jax.Array, ids: jax.Array, deltas: jax.Array,
                       combine, *, dense: bool = True):
    """The fused PS round: data[ids] = combine(data[ids], deltas) AND
    return the post-update rows — ONE row read serves both the update and
    the Get (the reference's test_matrix_perf Add-then-Get-same-rows
    round pays two). Returns (new_data, rows); trash/pad lanes of
    ``rows`` are arbitrary (callers mask). Dense runs ride the bulk
    slice path end to end."""
    pallas_write = use_pallas(data, ids)
    bucket = ids.shape[0]
    trash = data.shape[0] - 1

    def dense_fn(_):
        sl = jax.lax.dynamic_slice(data, (start, 0), (bucket, data.shape[1]))
        # pad/foreign lanes' deltas are trash-bound — zero them so the
        # bulk path never applies them to live rows; their positions get
        # combine(row, 0) == row (the contract)
        dz = jnp.where((ids != trash)[:, None], deltas, 0)
        new = combine(sl, dz)
        out = jax.lax.dynamic_update_slice(data, new, (start, 0))
        return out, new   # prefix layout: the Get half IS ``new``

    def general(_):
        rows = jnp.take(data, ids, axis=0, mode="clip")
        new = combine(rows, deltas)
        return _set_rows(data, ids, new, pallas_write), new

    if (not dense or not _dense_backend_ok()
            or bucket >= data.shape[0]):
        return general(None)   # static guards (see gather_rows)
    ok, start, _ = _dense_run(ids, data.shape[0])
    return jax.lax.cond(ok, dense_fn, general, None)


def update_rows_with_state(data: jax.Array, aux, ids: jax.Array, aux_lanes,
                           deltas: jax.Array, update, *, dense: bool = True):
    """``update_gather_rows`` for an updater that holds state:
    ``data[ids], aux[lanes] = update(data[ids], aux[lanes], deltas)``.
    ``aux`` is a pytree of row-shaped 2-D leaves and ``aux_lanes`` gives,
    a leaf, the rows of ``ids`` in it (trash lanes: the leaf's last row);
    ``update(rows, aux_rows, deltas) -> (rows, aux_rows)`` is elementwise
    over the rows. Returns (new_data, new_aux, rows); trash/pad lanes of
    ``rows`` are arbitrary (callers mask).

    The dense run is decided ONCE for the apply, on ``ids``: a leaf's
    lanes are ``ids`` plus an offset, so its run starts at its lanes'
    first element. That branch slices the rows and every leaf once,
    updates the slices, and writes each back with one update-slice; the
    lanes past the run's end keep the SLICE's values, rows and state (an
    updater need not be the identity on a zero delta: momentum's smooth
    decays), so nothing is gathered and no table is read twice."""
    bucket = ids.shape[0]

    def first(leaf, lanes):
        # a bucket as long as the leaf's live rows can only start at row
        # 0: a constant, which lets XLA write the update-slice in place
        # from the update's own fusion (a dynamic start is a copy apart)
        return 0 if bucket == leaf.shape[0] - 1 else lanes[0]

    def dense_fn(data, aux):
        def cut(leaf, at):
            return jax.lax.dynamic_slice(leaf, (at, 0),
                                         (bucket, leaf.shape[1]))

        def put(leaf, rows, at):
            return jax.lax.dynamic_update_slice(leaf, rows, (at, 0))
        tables = (data, aux)     # the rows and the state, one tree
        starts = jax.tree.map(first, tables, (ids, aux_lanes))
        old = jax.tree.map(cut, tables, starts)
        keep = (jnp.arange(bucket) < count)[:, None]
        new = jax.tree.map(lambda n, o: jnp.where(keep, n, o),
                           tuple(update(*old, deltas)), old)
        return (*jax.tree.map(put, tables, new, starts), new[0])

    def general(data, aux):
        rows = jnp.take(data, ids, axis=0, mode="clip")
        aux_rows = jax.tree.map(lambda leaf, lanes: jnp.take(leaf, lanes,
                                                             axis=0),
                                aux, aux_lanes)
        new, new_aux = update(rows, aux_rows, deltas)
        # trash lanes computed garbage from the trash row: it goes
        # straight back to the trash row, never to live data
        return (_set_rows(data, ids, new, use_pallas(data, ids)),
                jax.tree.map(lambda leaf, lanes, rows: _set_rows(
                    leaf, lanes, rows, use_pallas(leaf, lanes)),
                    aux, aux_lanes, new_aux), new)

    if (not dense or not _dense_backend_ok()
            or bucket >= data.shape[0]):
        return general(data, aux)   # static guards (see gather_rows)
    ok, _, count = _dense_run(ids, data.shape[0])
    return jax.lax.cond(ok, dense_fn, general, data, aux)


def slice_rows(data: jax.Array, start, count, bucket: int,
               num_cols: int) -> jax.Array:
    """The read of a DENSE run, beside ``gather_rows``: ``bucket`` rows of
    ``data`` from row ``start``, their first ``num_cols`` columns (the
    storage pad cut off), the lanes at and past ``count`` zero — what the
    table's gather hands back for ``start .. start + count - 1`` padded to
    ``bucket`` lanes. ``count=None`` says the run IS its bucket: nothing to
    mask. No ``lax.cond`` and no ids: the caller decides on the host that
    its ids are a run (``MatrixServerTable._fetch_run``) and that the
    slice stays inside the live rows, ``start + bucket <= data.shape[0] -
    1``, so ``dynamic_slice`` cannot clamp onto the trash row.

    A bucket as long as the live rows can only start at row 0: the start
    is then a constant (as ``update_rows_with_state``'s ``first``), and
    with nothing to mask the program is one static slice of the table."""
    if bucket == data.shape[0] - 1:
        start = 0
    rows = jax.lax.dynamic_slice(data, (start, 0), (bucket, num_cols))
    if count is None:
        return rows
    return jnp.where((jnp.arange(bucket) < count)[:, None], rows, 0)


def row_write(shard_rows: int, cols: int, dtype, bucket: int) -> str:
    """Which write a row-update program of ``bucket`` id lanes takes on a
    shard of ``shard_rows`` x ``cols`` (trash row counted), by the tests
    the update verbs above make on the same static shapes; the table layer
    counts its applies by it (``table.device_apply.*_verbs``):

    * ``"small_table"``: the bucket is not under the shard's rows, so the
      program is the general branch alone, with no dense-run ``cond``
      (a slice of ``bucket`` rows does not fit the table); its rows are
      written by the kernel or by XLA's scatter as below;
    * ``"pallas"``: the general branch writes through
      ``pallas_scatter_set_rows`` (``use_pallas``);
    * ``"xla"``: it writes through XLA's scatter: a row shape Mosaic
      does not compile, an id vector over ``SMEM_IDS_BYTES``, or no TPU.

    On one shard the last two sit behind the dense-run test, which the
    device decides from the ids."""
    if bucket >= shard_rows:
        return "small_table"
    data = jax.ShapeDtypeStruct((shard_rows, cols), dtype)
    ids = jax.ShapeDtypeStruct((bucket,), jnp.int32)
    return "pallas" if use_pallas(data, ids) else "xla"


def pool_rows(rows: jax.Array, bag_of: jax.Array, num_bags: int) -> jax.Array:
    """Sum pooling of gathered rows: ``(num_bags, cols)`` whose row ``b``
    is the sum of the ``rows`` whose ``bag_of`` is ``b``, a bag no
    position names a row of zeros. ``bag_of`` is the position-to-bag map
    of bags laid end to end, so it is SORTED, and its pad lanes carry
    ``num_bags``: a segment past the last, which the sum drops, and the
    map stays sorted with them. Both sizes are static, the positions' rung
    and the bags' rung; the table layer pads to them
    (``tables/pooled.py``). One XLA scatter-add on every backend: a bag of
    a row-sharded table holds a position or two, no shape for a kernel."""
    return jax.ops.segment_sum(rows, bag_of, num_segments=num_bags,
                               indices_are_sorted=True)


def spread_rows(bag_rows: jax.Array, bag_of: jax.Array) -> jax.Array:
    """``pool_rows``' transpose, the backward of a sum: every position
    takes its bag's row, ``bag_rows[bag_of]``. A pad lane (``bag_of`` past
    the last bag) reads the last bag's row; the caller drops it by the map
    it sums the positions by (a segment of -1)."""
    return jnp.take(bag_rows, bag_of, axis=0, mode="clip")
