"""Sum pooling on the server: the device plane's two pooled verbs.

A recommendation model looks a *bag* of rows up and wants their SUM
(torch's ``EmbeddingBag``, torchrec's ``EmbeddingBagCollection``,
``tf.nn.embedding_lookup_sparse(combiner="sum")``), and hands back one
gradient a bag, which every row of the bag takes (the backward of a sum).
``MatrixServerTable.device_fetch_pooled`` / ``.device_apply_pooled`` do
both where the rows live: a bag's rows are summed, and its gradient
spread, inside ONE device program a verb, so what crosses the device
plane's boundary is a row a bag and not a row a position.

The bags come jagged, as ``(row_ids, lengths)``: the positions bag after
bag, and how many belong to each bag (0 allowed). That is how a
row-sharded server meets them: of a sample's bag of ``h`` ids over a table
split by rows over ``N`` servers, ``h / N`` fall here, so most bags hold a
position or two and many none (``tables/pooled_reference.py``
``split_bags`` is that arithmetic).

Shapes. Everything a program is compiled for is a rung (``program_key``):
the positions pad to the id ladder the row verbs use (``next_bucket``; pad
id -1, the trash row), the bags to a ladder of their own (``bag_bucket``;
a pad bag has no position and pools to zeros), the distinct rows of an
apply to the power of two the merged Add takes (``_merged_add_rows``: the
count changes from verb to verb). A pad position's bag is the bags' rung,
a segment past the last, which ``ops.rows.pool_rows`` drops; its inverse
map is -1, which the apply's combine drops. Position count and bag count
both change from verb to verb, so a caller that must not compile while it
serves warms every ``program_key`` its traffic meets.

The programs are built at a table's first pooled verb (``_programs``) from
the table's own traceable row programs (``device_gather_rows``,
``device_update_rows``): a table that never pools builds nothing, and this
module is imported by the first pooled verb, not with the package. On a
table laid over several devices of ONE process they therefore go through
the ``shard_map`` gather and update as the row verbs do. In a
multi-process world the verbs refuse (``CHECK``): partial bags of several
processes would need a parts round of their own.

Telemetry (``docs/DESIGN.md`` 6, 11): spans ``server.table.
device_fetch_pooled`` / ``.device_apply_pooled`` with ``.prepare`` (the
apply's holds ``.prepare.unique`` and ``server.table.device_apply_pooled.
combine``) and ``.dispatch`` (``.place`` / ``.call`` through
``tables/crossing.py``); counters ``table.device_fetch_pooled.{bags,
positions,empty_bags,bytes}`` and ``table.device_apply_pooled.{bags,
positions,unique_rows,bytes,d2h_bytes}``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from multiverso_tpu.ops import rows as row_ops
from multiverso_tpu.parallel import multihost
from multiverso_tpu.parallel.mesh import next_bucket
from multiverso_tpu.tables import crossing
from multiverso_tpu.tables.matrix_table import _cut_rows, _pad_rows
from multiverso_tpu.telemetry import metrics as tmetrics
from multiverso_tpu.telemetry import trace as ttrace
from multiverso_tpu.updaters.base import AddOption
from multiverso_tpu.utils.log import CHECK


def bag_bucket(bags: int) -> int:
    """The rung a bag count pads to: the bags' own ladder, ``next_bucket``'s
    steps (powers of two to 256, quarter octaves above) on the bag count.
    A row-sharded server's bag count is a random thinning of the batch and
    differs from verb to verb by a few in a thousand; a rung is a shape."""
    return next_bucket(bags)


def program_key(positions: int, bags: int, distinct: int
                ) -> Tuple[int, int, int]:
    """What of a pooled verb's bags picks its programs: (position rung, bag
    rung, distinct class). The fetch's program is keyed by the first two,
    the apply's by all three."""
    return (next_bucket(positions), bag_bucket(bags),
            max(8, 1 << (int(distinct) - 1).bit_length()))


class _Programs:
    """A table's two pooled programs, over its traceable row programs."""

    def __init__(self, table):
        gather, update = table.device_gather_rows, table.device_update_rows

        @jax.named_scope("table.fetch_pooled")
        def _fetch_pooled(data, aux, ids, bag_of, *, bags):
            rows = gather(data, aux, ids).astype(jnp.float32)
            return row_ops.pool_rows(rows, bag_of, bags)

        @jax.named_scope("table.apply_pooled")
        def _apply_pooled(state, uniq_ids, bag_deltas, bag_of, inv, opt):
            """``_merged_add_rows`` whose delta a position is gathered from
            its bag's: spread by the bag map, summed by the inverse map
            (-1 drops a pad position), the row update at the distinct
            rows' bucket. The spread rows exist inside the program only."""
            combined = jax.ops.segment_sum(
                row_ops.spread_rows(bag_deltas, bag_of), inv,
                num_segments=uniq_ids.shape[0])
            return update(state, uniq_ids, combined, opt)

        self.fetch = jax.jit(_fetch_pooled, static_argnames=("bags",))
        self.apply = jax.jit(_apply_pooled, donate_argnums=(0,))


def _programs(table) -> _Programs:
    programs = table.__dict__.get("_pooled_programs")
    if programs is None:    # the table's first pooled verb
        programs = table.__dict__["_pooled_programs"] = _Programs(table)
    return programs


def _check_bags(table, row_ids, lengths):
    """A pooled verb's validated ``(ids, lengths)``."""
    CHECK(multihost.world_size() == 1,
          "the pooled verbs run in a one-process world: the partial bags "
          "of several processes would need a parts round of their own "
          "(device_fetch_rows / device_apply_rows are collective there)")
    ids = np.asarray(row_ids, np.int32).ravel()
    lengths = np.asarray(lengths)
    CHECK(lengths.size > 0 and lengths.dtype.kind in "iu",
          "lengths must be a non-empty integer array, one entry a bag")
    lengths = lengths.astype(np.int64).ravel()
    CHECK(int(lengths.min()) >= 0, "negative bag length")
    CHECK(int(lengths.sum()) == len(ids),
          f"bag lengths add up to {int(lengths.sum())}, not to the "
          f"{len(ids)} row ids")
    if len(ids):
        table._check_ids(ids)
    return ids, lengths


def _bag_map(lengths: np.ndarray, positions: int, bags: int) -> np.ndarray:
    """The bag of every position, bag ``b`` ``lengths[b]`` times, padded to
    ``positions`` lanes with ``bags``, the dropped segment: sorted."""
    out = np.full(positions, bags, np.int32)
    out[: int(lengths.sum())] = np.repeat(
        np.arange(len(lengths), dtype=np.int32), lengths)
    return out


def _rank_of(table, uniq: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """``np.searchsorted(uniq, ids)`` without a search, by the table's rank
    scratch (``MatrixServerTable._inverse_of_repeats``' own, shared with
    it; that method also steps a counter of the ROW apply, so the map is
    made here)."""
    rank = getattr(table, "_rank_scratch", None)
    if rank is None:
        rank = table._rank_scratch = np.empty(table.num_rows, np.int32)
    rank[uniq] = np.arange(len(uniq), dtype=np.int32)
    return rank.take(ids)


def fetch_pooled(table, row_ids, lengths, padded: bool = False) -> jax.Array:
    """``MatrixServerTable.device_fetch_pooled`` (its docstring)."""
    with table._verb_span("server.table.device_fetch_pooled"):
        with ttrace.span("server.table.device_fetch_pooled.prepare",
                         cat="server"):
            ids, lengths = _check_bags(table, row_ids, lengths)
            bags = len(lengths)
            rung = bag_bucket(bags)
            tmetrics.counter("table.device_fetch_pooled.bags").inc(bags)
            tmetrics.counter("table.device_fetch_pooled.positions").inc(
                len(ids))
            tmetrics.counter("table.device_fetch_pooled.empty_bags").inc(
                int(np.count_nonzero(lengths == 0)))
            tmetrics.counter("table.device_fetch_pooled.bytes").inc(
                bags * table.num_cols * 4)
            if len(ids):
                positions = next_bucket(len(ids))
                small = (table._pad_ids(ids, positions),
                         _bag_map(lengths, positions, rung))
        with ttrace.span("server.table.device_fetch_pooled.dispatch",
                         cat="server"):
            if not len(ids):        # empty bags alone: nothing to gather
                with crossing.call("zeros"):
                    return jnp.zeros((rung if padded else bags,
                                      table.num_cols), jnp.float32)
            device_ids, bag_of = table._place_small(small)
            with crossing.call("_fetch_pooled"):
                pooled = _programs(table).fetch(
                    table.state["data"], table.state["aux"], device_ids,
                    bag_of, bags=rung)
            return pooled if padded else _cut_rows(pooled, bags)


def apply_pooled(table, row_ids, lengths, bag_deltas,
                 option: Optional[AddOption] = None) -> None:
    """``MatrixServerTable.device_apply_pooled`` (its docstring)."""
    with table._verb_span("server.table.device_apply_pooled"):
        with ttrace.span("server.table.device_apply_pooled.prepare",
                         cat="server"):
            ids, lengths = _check_bags(table, row_ids, lengths)
            bags = len(lengths)
            rung = bag_bucket(bags)
            on_device = isinstance(bag_deltas, jax.Array)
            if not on_device:
                bag_deltas = np.asarray(bag_deltas, table.dtype)
            CHECK(bag_deltas.ndim == 2
                  and bag_deltas.shape[1] == table.num_cols
                  and bag_deltas.shape[0] in (bags, rung),
                  f"bag_deltas must be ({bags}, {table.num_cols}), a row "
                  f"a bag (or the {rung} rows of the bags' rung, as a "
                  "padded fetch returns them)")
            if not len(ids):        # empty bags alone: no row is named
                return
            with ttrace.child(".unique"):
                uniq = np.unique(ids)
            tmetrics.counter("table.device_apply_pooled.bags").inc(bags)
            tmetrics.counter("table.device_apply_pooled.positions").inc(
                len(ids))
            tmetrics.counter("table.device_apply_pooled.unique_rows").inc(
                len(uniq))
            tmetrics.counter("table.device_apply_pooled.bytes").inc(
                bags * table.num_cols * table.dtype.itemsize)
            # registered (at 0), as the row apply's: a path that brought a
            # copy to the host back would have a counter to step
            tmetrics.counter("table.device_apply_pooled.d2h_bytes")
            with ttrace.span("server.table.device_apply_pooled.combine",
                             cat="server"):
                # the program always sums by distinct row (a position set
                # with no repeat is rare under bags and not worth a third
                # program), so the inverse map is made for every id set
                positions, _, distinct = program_key(len(ids), bags,
                                                     len(uniq))
                small = (table._pad_ids(uniq, distinct),
                         _bag_map(lengths, positions, rung),
                         table._pad_ids(_rank_of(table, uniq, ids),
                                        positions))
        with ttrace.span("server.table.device_apply_pooled.dispatch",
                         cat="server"):
            opt = table._device_opt(option)
            if not on_device:
                bag_deltas = crossing.place(bag_deltas)
            elif bag_deltas.dtype != table.dtype:
                with crossing.call("astype"):
                    bag_deltas = bag_deltas.astype(table.dtype)
            bag_deltas = _pad_rows(bag_deltas, rung)
            uniq_ids, bag_of, inv = table._place_small(small)
            with crossing.call("_apply_pooled"):
                table.state = _programs(table).apply(
                    table.state, uniq_ids, bag_deltas, bag_of, inv, opt)
