"""Plain arithmetic of sum pooling over bags, and of a bag split over the
servers that share a table by rows.

numpy float32 only: no tables, no engine, no jax; of the program nothing
but ``updaters/reference.py``'s rules. Bags are jagged: ``ids`` holds the
positions bag after bag, ``lengths[b]`` how many belong to bag ``b`` (0
allowed). ``pool`` is what ``MatrixServerTable.device_fetch_pooled``
returns, ``spread`` the per-position deltas ``device_apply_pooled``
stands for, so that ``apply_bags`` is the row reference's Add of them.

``split_bags`` is what a row-sharded deployment does to a bag: a table of
``num_rows`` rows block-sharded over ``servers`` servers
(``share_reference.share_bounds``) leaves of every whole bag, on server
``s``, the positions whose id falls in ``s``'s block, under the server's
own offsets: a partial bag, often empty. The servers' partial pooled sums
add up to the whole bag's sum over the uncut table, and every server's
rows after the pooled apply of the whole bags' gradients are its block of
the uncut replay (``tests/test_pooled_tables.py`` holds the system to
both over 4 and over 32 servers).
"""

from __future__ import annotations

import numpy as np

from multiverso_tpu.tables.share_reference import share_bounds
from multiverso_tpu.updaters import reference

F32 = np.float32


def _bags(ids, lengths):
    ids = np.asarray(ids, np.int64).ravel()
    lengths = np.asarray(lengths, np.int64).ravel()
    if lengths.size and lengths.min() < 0:
        raise ValueError("negative bag length")
    if int(lengths.sum()) != len(ids):
        raise ValueError(f"bag lengths add up to {int(lengths.sum())}, "
                         f"not to the {len(ids)} ids")
    return ids, lengths


def bag_of(lengths) -> np.ndarray:
    """The bag of every position: bag ``b`` ``lengths[b]`` times."""
    lengths = np.asarray(lengths, np.int64).ravel()
    return np.repeat(np.arange(len(lengths)), lengths)


def pool(rows, ids, lengths) -> np.ndarray:
    """``(len(lengths), cols)`` float32: row ``b`` the sum of ``rows`` at
    bag ``b``'s positions, a repeated id as often as it stands, an empty
    bag zeros. Gather, then a per-bag sum taken in float64 and rounded
    once, because the order a device sums in is not the positions'."""
    ids, lengths = _bags(ids, lengths)
    rows = np.asarray(rows, F32)
    out = np.zeros((len(lengths), rows.shape[1]), np.float64)
    full = np.flatnonzero(lengths)
    if len(full):
        starts = (np.cumsum(lengths) - lengths)[full]
        out[full] = np.add.reduceat(rows[ids].astype(np.float64), starts,
                                    axis=0)
    return out.astype(F32)


def spread(bag_deltas, lengths) -> np.ndarray:
    """``(sum(lengths), cols)``: every position of bag ``b`` carries
    ``bag_deltas[b]`` (the backward of a sum)."""
    bag_deltas = np.asarray(bag_deltas, F32)
    lengths = np.asarray(lengths, np.int64).ravel()
    if len(bag_deltas) != len(lengths):
        raise ValueError("one delta row a bag")
    return bag_deltas[bag_of(lengths)]


def apply_bags(updater: str, state: dict, ids, lengths, bag_deltas,
               **option) -> dict:
    """One pooled Add under rule ``updater``: the row reference's Add of
    the spread deltas (repeats, within a bag and across bags, summed
    before the rule runs once a row). Empty bags alone: nothing."""
    ids, lengths = _bags(ids, lengths)
    if len(ids):
        reference.apply_rows(updater, state, ids,
                             spread(bag_deltas, lengths), **option)
    return state


def split_bags(ids, lengths, num_rows: int, servers: int, server: int,
               keep_empty: bool = True):
    """What ``server`` of ``servers`` sees of whole bags drawn over the
    uncut table of ``num_rows`` rows: ``(ids, lengths, bags)``. ``ids``:
    the positions that fall in its block, in their order, moved to its
    offsets; ``lengths``: how many each partial bag holds; ``bags``: the
    whole bag each partial bag is a part of. ``keep_empty`` keeps a bag
    none of whose positions fall here (length 0: what a server that
    returns a dense ``[batch, dim]`` is handed); without it such bags are
    dropped, as a trainer that made the split and knows sends them."""
    ids, lengths = _bags(ids, lengths)
    first, past = share_bounds(num_rows, servers, server)
    mine = (ids >= first) & (ids < past)
    part = np.bincount(bag_of(lengths)[mine], minlength=len(lengths))
    bags = np.arange(len(lengths))
    if not keep_empty:
        bags = bags[part > 0]
    return ((ids[mine] - first).astype(np.int32),
            part[bags].astype(np.int32), bags)
