"""Plain replay of a table whose server POOLS, under the server's AdaGrad:
what ``criteo1tb-mh-26t-128-pooled-share32`` must hold after a run; and
the plain arithmetic of bags (``pool``, ``spread``, ``split_bags``).

The benchmark's own copy (float32 numpy, nothing of the program imported)
of ``multiverso_tpu/tables/pooled_reference.py`` over
``updaters/reference.py``'s AdaGrad. A step hands a table bags as ``(ids,
lengths)``: the positions bag after bag and how many each bag holds (the
cell sends no empty bag). The pooled row of a bag is the float32 sum of
the rows its positions name, a repeated id as often as it stands. The
runner's gradient is ``adagrad_rows.delta_of``'s law on the POOLED row,
clipped, under a slope of its own (``SLOPE``, ``BOUND``), the step, the
column and the table, one row a bag; every position of a bag receives it,
the contributions to one row, within a bag and across bags, are summed
(float64, rounded once: the order a device sums in is not the order of
the positions) and the updater runs once on that row: ``g = delta / lr; h
+= g * g; w -= rho * g / sqrt(h + 1e-6)``.

A bag's gradient depends on every row of the bag, so no row replays alone:
the replay is of every row that some id set names, in place, a step
advancing the rows it names (the runner renumbers the named rows 0..m-1;
a row no set names is no part of it and keeps its initial value bit for
bit). Every COLUMN replays alone, so a table may be advanced band of
columns by band (``columns``), a thread each, and a subset of the columns
is a whole replay of those columns.

``advance_plain`` is that arithmetic as it reads, with ``np.add.at`` (the
pooled row summed in float32 in position order, which for the bags of one
or two positions a row-sharded server mostly sees IS the float64 sum
rounded once); it takes seconds a step at the deployment's size.
``advance`` is the same sums in the same order, bit for bit
(``benchmark/tests``), made of whole-array operations into buffers that
are kept: the k-th id of every bag that has one is gathered and added in
pass k (bags sorted by length, so a pass is a prefix), and likewise the
k-th position of every distinct row but the few named more than ``HEAVY``
times, each of which is summed by itself; what of that depends on the ids
alone is a ``plan``, made once an id set.

The ways to get it wrong that the cell's tolerance has to refuse
(``benchmark/tests/test_rec_pooled_steps.py``) are arguments of
``advance_plain``: ``store`` (rows and history kept in another dtype
between steps), ``neighbour_bag`` (a bag's gradient handed to the bag
after it), ``miss_last`` (a bag's sum misses its last position),
``across_unsummed`` (a row that several bags name takes the last bag's
contribution alone: the repeats across bags are left unsummed), and a
``table`` number that is a neighbour's; a dropped step is a step not
called.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.adagrad_rows import AMPLITUDE, EPS, F32

#: the runner's gradient a bag: ``SLOPE * clip(pooled, -BOUND, BOUND)`` plus
#: ``adagrad_rows.pattern``'s odd sixteenths of AMPLITUDE (1/64 at least).
#: With the other cells' slope of 1/4 on a pooled vector the gradient
#: passes through zero (its rows gather where it does), and a row that a
#: bag names for the first time there takes its first step in AdaGrad's
#: epsilon regime, ``0.1 * g / sqrt(g * g + 1e-6)``, which multiplies a
#: difference in ``g`` by up to 100: one chip run in eight read 1.27e-4 off
#: the replay at a handful of entries (PR 58's chip run, call c1, seed
#: 2147582003; PERF.md section 6, PR 59). Under
#: this slope and bound the pattern's sign is the gradient's and ``|g|`` is
#: 1/128 at least, so a step multiplies a difference in the pooled vector
#: by a tenth at most, while a wrong pool still moves ``g`` by a
#: hundredth of itself. Both constants are powers of two: the product is
#: exact, so a fused multiply-add on the device rounds as numpy does.
SLOPE, BOUND = F32(1 / 128), F32(1)

#: distinct rows a block of the updater's arithmetic in ``advance``
BLOCK = 4096


def pattern(step: int, columns, table: int) -> np.ndarray:
    """``adagrad_rows.pattern`` at the columns ``columns`` (their numbers
    in the table)."""
    k = (step * 7 + np.asarray(columns, np.int64) * 13 + table * 5) % 16
    return (2 * k - 15).astype(F32) * F32(AMPLITUDE / 16)


def bag_of(lengths) -> np.ndarray:
    """The bag of every position: bag ``b`` ``lengths[b]`` times."""
    lengths = np.asarray(lengths, np.int64).ravel()
    return np.repeat(np.arange(len(lengths)), lengths)


def pool(rows, ids, lengths) -> np.ndarray:
    """``(len(lengths), cols)`` float32: row ``b`` the sum of ``rows`` at
    bag ``b``'s positions, an empty bag zeros: gather, then a per-bag sum
    taken in float64 and rounded once."""
    ids = np.asarray(ids, np.int64).ravel()
    lengths = np.asarray(lengths, np.int64).ravel()
    assert lengths.min() >= 0 and lengths.sum() == len(ids)
    rows = np.asarray(rows, F32)
    out = np.zeros((len(lengths), rows.shape[1]), np.float64)
    full = np.flatnonzero(lengths)
    if len(full):
        out[full] = np.add.reduceat(rows[ids].astype(np.float64),
                                    (np.cumsum(lengths) - lengths)[full],
                                    axis=0)
    return out.astype(F32)


def spread(bag_deltas, lengths) -> np.ndarray:
    """Every position of bag ``b`` carries ``bag_deltas[b]``."""
    return np.asarray(bag_deltas, F32)[bag_of(lengths)]


def split_bags(ids, lengths, num_rows: int, servers: int, server: int,
               keep_empty: bool = True):
    """What ``server`` of ``servers`` sees of whole bags over a table of
    ``num_rows`` rows block-sharded by rows (``ceil(num_rows / servers)``
    consecutive rows a server): (its positions under its offsets, the
    partial bags' lengths, the whole bag of each)."""
    ids = np.asarray(ids, np.int64).ravel()
    lengths = np.asarray(lengths, np.int64).ravel()
    block = -(-int(num_rows) // int(servers))
    first = min(server * block, num_rows)
    past = min((server + 1) * block, num_rows)
    mine = (ids >= first) & (ids < past)
    part = np.bincount(bag_of(lengths)[mine], minlength=len(lengths))
    bags = np.arange(len(lengths))
    if not keep_empty:
        bags = bags[part > 0]
    return ((ids[mine] - first).astype(np.int32),
            part[bags].astype(np.int32), bags)


def advance_plain(w, h, ids, lengths, step: int, table: int, *,
                  learning_rate: float, rho: float, columns=None,
                  store=np.float32, neighbour_bag: bool = False,
                  miss_last: bool = False,
                  across_unsummed: bool = False) -> None:
    """One step of ``table`` over the bags ``(ids, lengths)``, in place on
    rows ``w`` and history ``h``: all their columns, or the columns whose
    numbers in the table are ``columns`` (every column replays alone)."""
    if columns is None:
        columns = np.arange(w.shape[1])
    ids = np.asarray(ids, np.int64).ravel()
    lengths = np.asarray(lengths, np.int64).ravel()
    assert lengths.min() >= 1 and lengths.sum() == len(ids)
    lr = F32(learning_rate)
    of = bag_of(lengths)
    rows = w[ids]
    if miss_last:
        rows[(np.cumsum(lengths) - 1)[lengths > 1]] = 0
    pooled = np.zeros((len(lengths), w.shape[1]), F32)
    np.add.at(pooled, of, rows)                 # position order, float32
    grads = lr * (SLOPE * np.clip(pooled, -BOUND, BOUND)
                  + pattern(step, columns, table)[None, :])
    if neighbour_bag:
        grads = np.roll(grads, 1, axis=0)
    contributions = grads[of].astype(np.float64)
    uniq, inv = np.unique(ids, return_inverse=True)
    if across_unsummed:
        # of the bags that name a row, only the last one's positions count
        last_bag = np.zeros(len(uniq), np.int64)
        np.maximum.at(last_bag, inv, of)
        contributions[of != last_bag[inv]] = 0
    summed = np.zeros((len(uniq), w.shape[1]), np.float64)
    np.add.at(summed, inv, contributions)
    g = summed.astype(F32) / lr
    hist = h[uniq] + g * g
    new = w[uniq] - F32(rho) * g / np.sqrt(hist + EPS)
    if np.dtype(store) != F32:
        new, hist = (x.astype(store).astype(F32) for x in (new, hist))
    w[uniq], h[uniq] = new, hist


def _passes(of_group, item, cap: int):
    """``item`` (one entry a member) regrouped for sums that run member
    after member. Groups are sorted by size, largest first (stable). A
    group of more than ``cap`` members is summed by itself: ``heavy`` holds
    its members' items, in their order. The others are summed together, in
    passes: ``passes[k]`` is the k-th member's item of every such group
    that has one, a prefix of their order. ``of_group``: the group of each
    member, members of one group in the order they are to be added.
    -> (order of the groups, heavy, passes)."""
    sizes = np.bincount(of_group)
    order = np.argsort(-sizes, kind="stable")
    by_group = np.argsort(of_group, kind="stable")
    starts, sizes = (np.cumsum(sizes) - sizes)[order], sizes[order]
    many = int(np.searchsorted(-sizes, -cap, side="left"))     # sizes > cap
    heavy = [item[by_group[a: a + n]]
             for a, n in zip(starts[:many], sizes[:many])]
    starts, sizes, passes = starts[many:], sizes[many:], []
    while len(sizes) and sizes[0] > len(passes):
        n = int(np.searchsorted(-sizes, -len(passes), side="left"))
        passes.append(item[by_group[starts[:n] + len(passes)]])
    return order, heavy, passes


#: positions over which a row is summed by itself (``_passes``' cap): the
#: first id of a bag is log-uniform, so a few hundred rows of a large table
#: are named up to 3,000 times a step, and a pass for each of those counts
#: would be thousands of small operations
HEAVY = 32


def plan(ids, lengths) -> dict:
    """What of a step depends on its ids alone. Bags are renumbered by
    length, distinct rows by how many positions name them, so that every
    pass of ``advance`` is a prefix: ``pool[k]`` the k-th id of every bag
    that has one; ``spread[k]`` the (renumbered) bag of the k-th position
    of every distinct row named at most ``HEAVY`` times, ``heavy`` the
    bags of all positions of each row named more often; ``uniq`` the
    distinct rows in their new order, the heavy ones first."""
    ids = np.asarray(ids, np.int64).ravel()
    lengths = np.asarray(lengths, np.int64).ravel()
    assert lengths.min() >= 1 and lengths.sum() == len(ids)
    of = bag_of(lengths)
    bag_order, _, pool_passes = _passes(of, ids, cap=int(lengths.max()))
    new_bag = np.empty(len(lengths), np.int64)
    new_bag[bag_order] = np.arange(len(lengths))
    uniq, inv = np.unique(ids, return_inverse=True)
    row_order, heavy, spread_passes = _passes(inv, new_bag[of], cap=HEAVY)
    return {"pool": pool_passes, "heavy": heavy, "spread": spread_passes,
            "uniq": uniq[row_order]}


def _buf(scratch: dict, name: str, rows: int, cols: int, dtype=F32):
    """``scratch[name]`` with at least ``rows`` rows, kept between steps
    (a fresh array of 100 MB a step is a page fault a page)."""
    have = scratch.get(name)
    if have is None or have.shape[0] < rows or have.shape[1] != cols:
        have = scratch[name] = np.empty((rows, cols), dtype)
    return have[:rows]


def _take(rows, idx, out=None):
    """``rows[idx]`` for indices known to be inside ``rows``: ``clip`` is
    the mode that gathers straight into ``out`` (``raise`` fills a buffer
    of its own first, under the interpreter's lock)."""
    return np.take(rows, idx, axis=0, out=out, mode="clip")


def advance(w, h, p: dict, step: int, table: int, scratch: dict, *,
            learning_rate: float, rho: float, columns=None) -> None:
    """``advance_plain`` by the plan ``p`` of its ids, bit for bit."""
    lr, rho, cols = F32(learning_rate), F32(rho), w.shape[1]
    if columns is None:
        columns = np.arange(cols)
    bags, uniq = len(p["pool"][0]), p["uniq"]
    grads = _buf(scratch, "bags", bags, cols)
    tmp = _buf(scratch, "tmp", max([bags, len(uniq)]
                                   + [len(bag) for bag in p["heavy"]]), cols)
    _take(w, p["pool"][0], grads)
    for idx in p["pool"][1:]:
        n = len(idx)
        _take(w, idx, tmp[:n])
        np.add(grads[:n], tmp[:n], out=grads[:n])
    np.multiply(np.clip(grads, -BOUND, BOUND, out=grads), SLOPE, out=grads)
    np.add(grads, pattern(step, columns, table)[None, :], out=grads)
    np.multiply(grads, lr, out=grads)
    g = _buf(scratch, "rows", len(uniq), cols)
    many = len(p["heavy"])
    for row, bag in enumerate(p["heavy"]):      # one after the other, f64
        g[row] = np.add.reduce(_take(grads, bag, tmp[:len(bag)]), axis=0,
                               dtype=np.float64)
    if p["spread"]:
        _take(grads, p["spread"][0], g[many:])
    if len(p["spread"]) > 1:
        # rows named more than once: a prefix, summed in float64
        n = len(p["spread"][1])
        acc = _buf(scratch, "acc", n, cols, np.float64)
        acc[...] = g[many: many + n]
        for idx in p["spread"][1:]:
            m = len(idx)
            _take(grads, idx, tmp[:m])
            np.add(acc[:m], tmp[:m], out=acc[:m])
        g[many: many + n] = acc
    np.divide(g, lr, out=g)
    # the updater, a block of rows at a time into kept buffers: an array
    # made anew in a loop is pages asked of the system under one lock,
    # and the bands' threads would wait on each other for them
    hist, rows_w, step_, root = (_buf(scratch, name, BLOCK, cols)
                                 for name in ("h", "w", "step", "root"))
    for a in range(0, len(uniq), BLOCK):
        rows, gb = uniq[a:a + BLOCK], g[a:a + BLOCK]
        n = len(rows)
        hb, wb, sb, rb = hist[:n], rows_w[:n], step_[:n], root[:n]
        np.multiply(gb, gb, out=rb)
        np.add(_take(h, rows, hb), rb, out=hb)          # h + g * g
        np.sqrt(np.add(hb, EPS, out=rb), out=rb)
        np.divide(np.multiply(gb, rho, out=sb), rb, out=sb)
        np.subtract(_take(w, rows, wb), sb, out=wb)     # w - rho * g / root
        w[rows] = wb
        h[rows] = hb
