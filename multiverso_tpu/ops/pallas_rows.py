"""Pallas TPU kernels: dynamic row gather / scatter / fused update on a
table shard.

These are the device half of the PS data plane. A ``Get`` over a row set is
one row-DMA per requested row out of the shard in HBM; an ``Add`` is the
mirrored write; the fused update kernel does read-modify-write in one pass
(row DMA in -> vector update in VMEM -> row DMA out), which is the
server-side Add of reference src/updater/updater.cpp:21-29 collapsed into a
single kernel instead of gather + XLA elementwise + scatter.

Row ids arrive as *scalar-prefetch* operands (SMEM) so DMA source/target
addresses are computed in-kernel.

Lowering constraints shape the design: a VMEM block must have its
second-to-last dim divisible by 8 (or equal to the array dim), so single
rows can't be blocks. Instead the grid runs over chunks of ``CHUNK`` ids;
the table shard itself stays in HBM (``memory_space=ANY``) and the kernel
issues one async row-copy per id — CHUNK outstanding DMAs per grid step,
waited together, while Mosaic pipelines the chunk blocks across steps.
CHUNK=64 measured ~1.3x over CHUNK=8 on v5e (deeper DMA pipelining); 128+
regresses (VMEM block pressure).

Coalescing: per-row DMAs cost ~68ns each on v5e regardless of locality —
pure descriptor-issue overhead (measured: random and contiguous id sets
gather at the same 7.5 GB/s). So each kernel checks, per chunk, whether
its ids are strictly consecutive (``_contig``: a scalar-core AND-chain
over the prefetched ids) and, when they are, rides ONE multi-row DMA for
the whole chunk instead of CHUNK row DMAs. Dense id sets — the WE
identity-remap blocks, reference test_matrix_perf's get-all phases, any
sorted run-heavy workload — collapse to sequential-copy bandwidth, while
random sparse sets keep the per-row path at unchanged cost (the check
adds ~5% scalar work per chunk). Ids are NOT sorted here: sorting would
force a same-sized permutation gather on the output (measured to cost as
much as the gather itself), so callers with natural locality get the win
and random callers pay nothing.

Contract (enforced by the caller, multiverso_tpu/tables/matrix_table.py):

* every id is in ``[0, num_rows)`` of the *local shard* — out-of-shard and
  padding lanes are pre-mapped to the shard's trash row;
* duplicate ids only occur on the trash row (the caller pre-combines
  duplicates), whose content is don't-care — so concurrent DMAs touching
  the same row (including the fused kernel's read-modify-write) can only
  collide on the trash row, never on live data. Ragged tails are handled
  in-kernel: gather over-fetches id 0 (read-only), scatter replicates the
  last pair (same bytes, same row), and the fused update *lane-guards* the
  tail with ``pl.when`` — a duplicated pad id there would write stale row
  bytes over the real lane's update.

On non-TPU backends the kernels run in interpreter mode (tests); the table
layer normally uses the XLA fallback there (rows.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _contig(vals):
    """Traced predicate: the chunk's ids are strictly consecutive
    (ids[j] == ids[0] + j). Measured cost ~0.2us of scalar-core compares
    per chunk against the ~4us a per-row chunk body costs — the coalesced
    single-DMA branch it unlocks is worth 20-60x on dense id sets (see
    module docstring 'Coalescing')."""
    ok = vals[1] - vals[0] == 1
    for j in range(2, len(vals)):
        ok = jnp.logical_and(ok, vals[j] - vals[j - 1] == 1)
    return ok

#: ids per grid step. Rows are exactly one 128-lane tile of a 4-byte dtype
#: (rows._pallas_eligible — the only row shape Mosaic compiles these
#: kernels for), so the fused kernel's three (CHUNK, 128) f32 VMEM blocks
#: are 96 KB: nothing to budget.
CHUNK = 64


def _make_gather_kernel(chunk, coalesce):
    """``coalesce`` is static (table has >= chunk rows): a smaller table
    could never satisfy _contig at runtime, and its multi-row slice would
    be ill-formed at trace time — so the branch is only emitted when it
    can exist."""
    def _gather_kernel(ids_ref, data_ref, out_ref, sem):
        i = pl.program_id(0)
        vals = [ids_ref[i * chunk + j] for j in range(chunk)]

        def per_row():
            copies = []
            for j in range(chunk):
                copies.append(pltpu.make_async_copy(
                    data_ref.at[pl.ds(vals[j], 1), :],
                    out_ref.at[pl.ds(j, 1), :],
                    sem.at[j]))
            for c in copies:
                c.start()
            for c in copies:
                c.wait()

        if not coalesce:
            per_row()
            return
        contig = _contig(vals)

        @pl.when(contig)
        def _():
            # consecutive ids: the whole chunk is ONE multi-row DMA
            cp = pltpu.make_async_copy(
                data_ref.at[pl.ds(vals[0], chunk), :],
                out_ref.at[pl.ds(0, chunk), :],
                sem.at[0])
            cp.start()
            cp.wait()

        pl.when(jnp.logical_not(contig))(per_row)
    return _gather_kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_gather_rows(data: jax.Array, ids: jax.Array,
                       interpret: bool = False) -> jax.Array:
    """rows[i] = data[ids[i]] — one row DMA per id, chunk per grid step."""
    chunk = CHUNK
    orig_n = ids.shape[0]
    if orig_n % chunk:
        # tail pad with id 0: a read-only over-fetch, sliced off below
        pad = chunk - orig_n % chunk
        ids = jnp.concatenate([ids, jnp.zeros(pad, ids.dtype)])
    n = ids.shape[0]
    cols = data.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // chunk,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),  # data: HBM
        ],
        out_specs=pl.BlockSpec((chunk, cols), lambda i, ids: (i, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA((chunk,))],
    )
    out = pl.pallas_call(
        _make_gather_kernel(chunk, coalesce=data.shape[0] >= chunk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, cols), data.dtype),
        interpret=interpret,
    )(ids, data)
    return out[:orig_n]


def _make_scatter_kernel(chunk, coalesce):
    def _scatter_kernel(ids_ref, rows_ref, data_ref, out_ref, sem):
        del data_ref  # alias donor; out_ref IS the table buffer
        i = pl.program_id(0)
        vals = [ids_ref[i * chunk + j] for j in range(chunk)]

        def per_row():
            copies = []
            for j in range(chunk):
                copies.append(pltpu.make_async_copy(
                    rows_ref.at[pl.ds(j, 1), :],
                    out_ref.at[pl.ds(vals[j], 1), :],
                    sem.at[j]))
            for c in copies:
                c.start()
            for c in copies:
                c.wait()

        if not coalesce:
            per_row()
            return
        contig = _contig(vals)

        @pl.when(contig)
        def _():
            cp = pltpu.make_async_copy(
                rows_ref.at[pl.ds(0, chunk), :],
                out_ref.at[pl.ds(vals[0], chunk), :],
                sem.at[0])
            cp.start()
            cp.wait()

        pl.when(jnp.logical_not(contig))(per_row)
    return _scatter_kernel


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def pallas_scatter_set_rows(data: jax.Array, ids: jax.Array,
                            rows: jax.Array,
                            interpret: bool = False) -> jax.Array:
    """data[ids[i]] = rows[i], in place (data is donated/aliased).

    Rows the ids never name keep their HBM content — only touched rows
    move, which is the whole point of the PS row protocol.
    """
    chunk = CHUNK
    if ids.shape[0] % chunk:
        # tail pad by replicating the last (id, row) pair: the extra DMAs
        # rewrite the same bytes to the same row — a no-op on memory content
        pad = chunk - ids.shape[0] % chunk
        ids = jnp.concatenate([ids] + [ids[-1:]] * pad)
        rows = jnp.concatenate([rows] + [rows[-1:]] * pad)
    n = ids.shape[0]
    cols = data.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // chunk,),
        in_specs=[
            pl.BlockSpec((chunk, cols), lambda i, ids: (i, 0)),   # rows: VMEM
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),  # data: HBM
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((chunk,))],
    )
    return pl.pallas_call(
        _make_scatter_kernel(chunk, coalesce=data.shape[0] >= chunk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(data.shape, data.dtype),
        input_output_aliases={2: 0},  # operand index counts the prefetch arg
        interpret=interpret,
    )(ids, rows, data)


def _make_update_kernel(combine, orig_n, chunk, coalesce):
    """RMW kernel. ``orig_n`` is the true id count: when it isn't a chunk
    multiple, tail lanes are skipped via pl.when (a duplicated pad id would
    RACE — the dup lane would write the row's pre-update bytes back over
    the real lane's update). Full-chunk batches compile with no guards.

    Coalescing: pad ids are zeros, which break strict +1 contiguity, so
    the single-DMA branch is unreachable for ragged chunks — pad lanes can
    only take the guarded per-row branch. ``coalesce`` statically drops
    the branch for tables smaller than one chunk (see _make_gather_kernel).
    """
    ragged = orig_n % chunk != 0

    def _update_kernel(ids_ref, deltas_ref, data_ref, out_ref, scratch,
                       rsem, wsem):
        del data_ref  # alias donor; out_ref IS the table buffer
        i = pl.program_id(0)
        vals = [ids_ref[i * chunk + j] for j in range(chunk)]

        def lane(j, fn):
            if ragged:
                pl.when(i * chunk + j < orig_n)(fn)
            else:
                fn()

        def cp(j, write):
            """The lane-j row DMA descriptor: table row <-> scratch row."""
            tbl = out_ref.at[pl.ds(vals[j], 1), :]
            buf = scratch.at[pl.ds(j, 1), :]
            if write:
                return pltpu.make_async_copy(buf, tbl, wsem.at[j])
            return pltpu.make_async_copy(tbl, buf, rsem.at[j])

        def per_row(write):
            for j in range(chunk):
                lane(j, lambda j=j: cp(j, write).start())
            for j in range(chunk):
                lane(j, lambda j=j: cp(j, write).wait())

        if not coalesce:
            per_row(False)
            scratch[...] = combine(scratch[...], deltas_ref[...])
            per_row(True)
            return

        contig = _contig(vals)

        def whole(write):
            tbl = out_ref.at[pl.ds(vals[0], chunk), :]
            buf = scratch.at[pl.ds(0, chunk), :]
            if write:
                return pltpu.make_async_copy(buf, tbl, wsem.at[0])
            return pltpu.make_async_copy(tbl, buf, rsem.at[0])

        @pl.when(contig)
        def _():
            whole(False).start()
            whole(False).wait()

        pl.when(jnp.logical_not(contig))(lambda: per_row(False))

        scratch[...] = combine(scratch[...], deltas_ref[...])

        @pl.when(contig)
        def _():
            whole(True).start()
            whole(True).wait()

        pl.when(jnp.logical_not(contig))(lambda: per_row(True))
    return _update_kernel


@functools.partial(jax.jit, static_argnames=("combine", "interpret"),
                   donate_argnums=(0,))
def pallas_update_rows(data: jax.Array, ids: jax.Array, deltas: jax.Array,
                       combine, interpret: bool = False) -> jax.Array:
    """data[ids[i]] = combine(data[ids[i]], deltas[i]), in place — the
    fused server-side Add (read rows -> vector update in VMEM -> write
    back), one pass over the touched rows.

    ``combine`` must be a jax-traceable elementwise fn of (rows, deltas)
    with ``combine(rows, 0) == rows`` (see module contract). It is a static
    arg: one compile per (shape, combine) pair — combines are per-table
    updater singletons, so this never retraces in steady state.
    """
    chunk = CHUNK
    orig_n = ids.shape[0]
    if orig_n % chunk:
        # tail pad to a chunk multiple; the padded lanes are skipped inside
        # the kernel (see _make_update_kernel — pad *values* are never read)
        pad = chunk - orig_n % chunk
        ids = jnp.concatenate([ids, jnp.zeros(pad, ids.dtype)])
        deltas = jnp.concatenate(
            [deltas, jnp.zeros((pad, deltas.shape[1]), deltas.dtype)])
    n = ids.shape[0]
    cols = data.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // chunk,),
        in_specs=[
            pl.BlockSpec((chunk, cols), lambda i, ids: (i, 0)),  # deltas
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),    # data: HBM
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        scratch_shapes=[pltpu.VMEM((chunk, cols), data.dtype),
                        pltpu.SemaphoreType.DMA((chunk,)),
                        pltpu.SemaphoreType.DMA((chunk,))],
    )
    return pl.pallas_call(
        _make_update_kernel(combine, orig_n, chunk,
                            coalesce=data.shape[0] >= chunk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(data.shape, data.dtype),
        input_output_aliases={2: 0},  # operand index counts the prefetch arg
        interpret=interpret,
    )(ids, deltas, data)
