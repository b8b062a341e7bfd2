"""The Pallas TPU kernel of the PS data plane: dynamic row scatter into a
table shard.

An ``Add`` over a row set ends in one write of the touched rows into the
shard in HBM; this kernel is that write wherever ``rows.use_pallas``
admits it (reads ride XLA's gather — ``rows.py`` holds the decision and
its reasons). Row ids arrive as *scalar-prefetch* operands (SMEM) so DMA
target addresses are computed in-kernel.

Lowering constraints shape the design: a VMEM block must have its
second-to-last dim divisible by 8 (or equal to the array dim), so single
rows can't be blocks. Instead the grid runs over chunks of ``CHUNK`` ids;
the table shard itself stays in HBM (``memory_space=ANY``) and the kernel
issues one async row-copy per id — CHUNK outstanding DMAs per grid step,
waited together, while Mosaic pipelines the chunk blocks across steps.
CHUNK=64 was tuned on a v5e in the rounds before the ledger (deeper DMA
pipelining than 8; 128+ regressed); no cell has re-measured it.

Coalescing: a per-row DMA costs descriptor-issue time whatever its
locality, so the kernel checks, per chunk, whether its ids are strictly
consecutive (``_contig``: a scalar-core AND-chain over the prefetched
ids) and, when they are, rides ONE multi-row DMA for the whole chunk
instead of CHUNK row DMAs. Ids are NOT sorted here: callers with natural
locality (the WE identity-remap blocks, any sorted run-heavy workload)
get the win and random callers pay the check only.

Contract (enforced by the caller, multiverso_tpu/tables/matrix_table.py):

* every id is in ``[0, num_rows)`` of the *local shard* — out-of-shard and
  padding lanes are pre-mapped to the shard's trash row;
* duplicate ids only occur on the trash row (the caller pre-combines
  duplicates), whose content is don't-care — so concurrent DMAs touching
  the same row can only collide on the trash row, never on live data. A
  ragged tail replicates the last (id, row) pair: same bytes, same row.

On non-TPU backends the kernel runs in interpreter mode (tests, through
``-use_pallas=on``); the table layer normally uses XLA's scatter there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _contig(vals):
    """Traced predicate: the chunk's ids are strictly consecutive
    (ids[j] == ids[0] + j): a few scalar-core compares per chunk, which
    unlock the single-DMA branch (module docstring 'Coalescing')."""
    ok = vals[1] - vals[0] == 1
    for j in range(2, len(vals)):
        ok = jnp.logical_and(ok, vals[j] - vals[j - 1] == 1)
    return ok

#: ids per grid step. Rows are exactly one 128-lane tile of a 4-byte dtype
#: (rows._pallas_eligible — the only row shape Mosaic compiles the kernel
#: for), so a (CHUNK, 128) f32 VMEM block is 32 KB: nothing to budget.
CHUNK = 64


def _make_scatter_kernel(chunk, coalesce):
    """``coalesce`` is static (table has >= chunk rows): a smaller table
    could never satisfy _contig at runtime, and its multi-row slice would
    be ill-formed at trace time — so the branch is only emitted when it
    can exist."""
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
