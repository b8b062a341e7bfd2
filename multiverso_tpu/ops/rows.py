"""Backend dispatch for the row gather/scatter/update table ops.

``use_pallas`` is governed by the ``use_pallas`` flag:
``auto`` (default) — reads via XLA's native gather everywhere, writes via
the coalesced Pallas DMA kernels on TPU (the measured-fastest split: TPU
vector loads gather random 512B rows at ~100 GB/s while XLA scatter
crawls at ~6 GB/s, so each half rides its fast lane); ``on`` — Pallas for
every verb incl. the fused single-kernel RMW (interpreter mode off-TPU;
used by tests); ``off`` — XLA only.

The XLA fallback relies on jit'd gather + ``.at[].set`` — on a CPU test
mesh that is both correct and fast enough.

Row DMAs slice HBM along the lane dim, and Mosaic compiles them only for
rows of exactly one 128-lane tile of a 4-byte dtype (``_pallas_eligible``).
The table layer pads its storage column dim to ``padded_cols``, which keeps
tables of up to 128 columns on the kernels; wider ones ride XLA. The pad
alone measured ~5.6x on the reference 1Mx50 row-op benchmark even for plain
XLA (aligned rows vs 200-byte ragged rows), with the fused Pallas update
another ~1.6x on top.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from multiverso_tpu.utils.configure import (GetFlag, MV_DEFINE_string,
                                            cached_str_flag)

#: one constant feeds both the flag registration and the cached
#: accessor's fallback, so the two defaults cannot drift apart
_USE_PALLAS_DEFAULT = "auto"
MV_DEFINE_string("use_pallas", _USE_PALLAS_DEFAULT,
                 "row-op kernels: auto (TPU only) / on / off")
MV_DEFINE_string("matrix_pad_cols", "auto",
                 "pad matrix storage cols to the 128-lane tile: auto/on/off")
#: use_pallas/_forced_on run per row-op dispatch (every verb on the
#: apply path) — listener-cached read, not a registry walk per call
_use_pallas_flag = cached_str_flag("use_pallas", _USE_PALLAS_DEFAULT)

LANE = 128
#: Pallas row kernels take the id vector as a SCALAR-PREFETCH operand in
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
    if ids is not None and ids.shape[0] * 4 > SMEM_IDS_BYTES:
        return False   # id vector would overflow the SMEM prefetch
    mode = _use_pallas_flag()
    if mode == "on":
        # forced on (interpreter mode off-TPU; tests): still respect the
        # lowering constraints — an ineligible shape would be a Mosaic
        # compile error rather than a kernel choice
        return data is None or _pallas_eligible(data)
    if mode == "off":
        return False
    return (jax.default_backend() == "tpu"
            and (data is None or _pallas_eligible(data)))


def padded_cols(num_cols: int, itemsize: int = 4) -> int:
    """Storage column count for a logical ``num_cols``, governed by the
    ``matrix_pad_cols`` flag: ``auto``/``on`` — pad 4-byte dtypes up to the
    128-lane tile; ``off`` — never. Aligned rows are what make the row hot
    path fast (ragged 200-byte rows measured ~5.6x slower even on the plain
    XLA path) and what the Pallas row-DMA kernels require. The pad trades
    HBM capacity for alignment; padded columns hold zeros and every updater
    is identity on a zero delta, so they stay zero."""
    mode = str(GetFlag("matrix_pad_cols")).lower()
    if mode == "off" or itemsize != 4:
        return num_cols
    return -(-num_cols // LANE) * LANE


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _forced_on(data, ids=None) -> bool:
    """``use_pallas=on`` (test mode): force the Pallas kernel for verbs
    whose default path is XLA, so tests keep covering the kernels."""
    if ids is not None and ids.shape[0] * 4 > SMEM_IDS_BYTES:
        return False
    return _use_pallas_flag() == "on" and _pallas_eligible(data)


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
    whose branches read-modify-write it — every call copies the whole
    table (measured ~300x). TPU aliases it fine (measured: dense rounds
    9-18 Gelem/s, random unharmed)."""
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
    up with the batch lanes 1:1, which measured ~3x faster than the
    roll-compensated general-segment variant on v5e (and rolls plus a
    read-back slice defeated XLA's in-place aliasing of the table
    buffer, turning every round into a whole-table copy)."""
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


def gather_rows(data: jax.Array, ids: jax.Array, *,
                dense: bool = True) -> jax.Array:
    """rows[i] = data[ids[i]]; all ids must be in range (caller maps
    out-of-shard lanes to the trash row). Trash/pad lanes may return
    ARBITRARY row content — every caller masks or trash-routes them.

    Reads ride XLA's native gather (``mode='clip'`` — the jnp default
    'fill' adds an out-of-bounds select measured 3x slower on v5e).
    ``use_pallas=on`` still forces the Pallas kernel so tests cover it.

    NO dense-run cond here, deliberately: a lax.cond over a LIVE
    (non-donated) table defeats XLA's buffer aliasing — each branch gets
    an operand copy of the whole table (measured ~150x on the CPU
    backend: 512MB copied per Get). The dense bulk-slice fast path lives
    only in the verbs that consume/donate the table (scatter_set_rows,
    update_rows, update_gather_rows), where the in-place chain survives
    the cond. ``dense`` is accepted for signature symmetry."""
    del dense
    if _forced_on(data, ids):
        from multiverso_tpu.ops.pallas_rows import pallas_gather_rows
        return pallas_gather_rows(data, ids, interpret=_interpret())
    return jnp.take(data, ids, axis=0, mode="clip")


def scatter_set_rows(data: jax.Array, ids: jax.Array,
                     rows: jax.Array, *, dense: bool = True) -> jax.Array:
    """data[ids[i]] = rows[i]; duplicates only on the trash row.

    Writes are the mirror image of reads on TPU: XLA's scatter measured
    ~3-6 GB/s (it serializes), while the Pallas row-DMA kernel does
    ~30 GB/s random (17ns/row DMA-issue floor on v5e) and 60-200 GB/s
    on coalesced contiguous runs — so writes keep the Pallas path
    wherever it is eligible. A runtime-detected dense run takes the bulk
    slice-merge-update path (~300 GB/s r+w) instead."""
    if _forced_on(data, ids):
        # test mode: keep the Pallas kernel covered even for dense runs
        from multiverso_tpu.ops.pallas_rows import pallas_scatter_set_rows
        return pallas_scatter_set_rows(data, ids, rows,
                                       interpret=_interpret())
    fallback_pallas = use_pallas(data, ids)

    def general(_):
        if fallback_pallas:
            from multiverso_tpu.ops.pallas_rows import pallas_scatter_set_rows
            return pallas_scatter_set_rows(data, ids, rows,
                                           interpret=_interpret())
        return data.at[ids].set(rows)

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
    Add for aux-free elementwise updaters. ``combine`` must satisfy
    combine(rows, 0) == rows (see pallas_rows contract) and be
    identity-stable (one object per table) so the jit cache holds.

    Default TPU path is the HYBRID: XLA vector-gather for the read half
    (clip mode, see gather_rows), combine fused elementwise, and the
    Pallas scatter for the write half. A runtime-detected dense run
    instead does ONE bulk dynamic_slice -> combine -> dynamic_update_slice
    (~290 GB/s r+w measured v5e — the 64-row chunk DMAs can't touch bulk
    copies). ``use_pallas=on`` forces the fused single-kernel RMW so
    tests cover it; the XLA fallback is gather + combine + scatter."""
    if _forced_on(data, ids):
        from multiverso_tpu.ops.pallas_rows import pallas_update_rows
        return pallas_update_rows(data, ids, deltas, combine,
                                  interpret=_interpret())
    # ONE implementation with update_gather_rows: the dropped rows output
    # is an intermediate both branches compute anyway (zero extra work)
    return _update_gather_impl(data, ids, deltas, combine,
                               use_pallas(data, ids), dense)[0]


def update_gather_rows(data: jax.Array, ids: jax.Array, deltas: jax.Array,
                       combine, *, dense: bool = True):
    """The fused PS round: data[ids] = combine(data[ids], deltas) AND
    return the post-update rows — ONE row read serves both the update and
    the Get (the reference's test_matrix_perf Add-then-Get-same-rows
    round pays two). Returns (new_data, rows); trash/pad lanes of
    ``rows`` are arbitrary (callers mask). Dense runs ride the bulk
    slice path end to end."""
    if _forced_on(data, ids):
        from multiverso_tpu.ops.pallas_rows import pallas_update_rows
        new_data = pallas_update_rows(data, ids, deltas, combine,
                                      interpret=_interpret())
        return new_data, jnp.take(new_data, ids, axis=0, mode="clip")
    return _update_gather_impl(data, ids, deltas, combine,
                               use_pallas(data, ids), dense)


def _update_gather_impl(data, ids, deltas, combine, pallas_write,
                        allow_dense):
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
        if pallas_write:
            from multiverso_tpu.ops.pallas_rows import pallas_scatter_set_rows
            out = pallas_scatter_set_rows(data, ids, new,
                                          interpret=_interpret())
        else:
            out = data.at[ids].set(new)
        return out, new

    if (not allow_dense or not _dense_backend_ok()
            or bucket >= data.shape[0]):
        return general(None)   # static guards (see gather_rows)
    ok, start, _ = _dense_run(ids, data.shape[0])
    return jax.lax.cond(ok, dense_fn, general, None)
