"""The benchmark's own copy of the SparseMatrixTable's plain reference
(``multiverso_tpu/tables/sparse_reference.py``, as ``adagrad_rows.py`` is
the copy of ``updaters/reference.py``: the yardstick does not import what
it measures), and the order-free comparisons that decide ``correct`` in
the cell ``mt_sparse_rounds``.

numpy only. ``SparseRows`` is a float32 matrix, a bool matrix
``up_to_date[worker, row]`` and the three transitions as the reference's
``UpdateAddState`` / ``UpdateGetState`` read
(src/table/sparse_matrix_table.cpp:200-259): ``benchmark/tests`` drives it,
whole and with one fault at a time, to show what the comparisons catch.
Worker threads interleave in a run, so nothing here depends on the order
the engine served the verbs in:

* ``replay_rows``: what sampled rows must hold after every Add (deltas are
  whole numbers: float32 sums are exact in any order, a bfloat16 replay is
  not);
* ``coverage``: from the runner's own record of ids, that what a worker's
  Gets returned is covered by the Adds of the *other* workers.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

class SparseRows:
    def __init__(self, num_rows: int, num_cols: int, workers: int):
        self.data = np.zeros((num_rows, num_cols), np.float32)
        # all fresh at start (the reference's constructor,
        # sparse_matrix_table.cpp:184-196)
        self.up_to_date = np.ones((workers, num_rows), bool)

    def add(self, worker: int, ids: Optional[np.ndarray],
            deltas: np.ndarray) -> None:
        """``ids`` None = the whole table, ``deltas`` then one row a table
        row; repeated ids sum."""
        rows = (range(self.data.shape[0]) if ids is None
                else [int(i) for i in np.asarray(ids).ravel()])
        deltas = np.asarray(deltas, np.float32).reshape(len(rows), -1)
        for at, row in enumerate(rows):
            self.data[row] += deltas[at]
        # UpdateAddState: stale for every worker but the one that added
        for row in rows:
            for w in range(self.up_to_date.shape[0]):
                if w != worker:
                    self.up_to_date[w, row] = False

    def get(self, worker: int, ids: Optional[np.ndarray] = None
            ) -> Tuple[np.ndarray, np.ndarray]:
        """-> (row ids, rows): the rows stale for ``worker`` (among
        ``ids``, or in the whole table), now marked fresh; row 0 when
        there is none."""
        if worker == -1:
            out = list(range(self.data.shape[0]))
        else:
            rows = (range(self.data.shape[0]) if ids is None
                    else [int(i) for i in np.asarray(ids).ravel()])
            out = []
            # UpdateGetState
            for row in rows:
                if not self.up_to_date[worker, row]:
                    out.append(row)
                    self.up_to_date[worker, row] = True
            if not out:
                out = [0]
        out = np.asarray(out, np.int32)
        return out, self.data[out].copy()


def replay_rows(sample: np.ndarray, cols: int, adds,
                dtype=np.float32) -> np.ndarray:
    """Rows ``sample`` (sorted unique ids) of a zero table after ``adds``:
    an iterable of (ids, delta, times), ``ids`` distinct within an Add,
    ``delta`` a (len(ids), cols) array applied ``times`` times. The sums
    are kept in ``dtype``: float32 is what the configuration states."""
    out = np.zeros((len(sample), cols), dtype)
    for ids, delta, times in adds:
        if not times:
            continue
        pos = np.searchsorted(sample, ids)
        pos[pos == len(sample)] = 0
        hit = sample[pos] == ids
        out[pos[hit]] += (np.asarray(delta, np.float32)[hit]
                          * np.float32(times)).astype(dtype)
    return out.astype(np.float32)


def coverage(returned, others, num_rows: int) -> Tuple[int, int]:
    """One worker's Gets against the other workers' Adds.

    ``returned``: the id array of every Get of the worker, the last one
    made after every Add was acknowledged. ``others``: (ids, times) of
    every Add of every *other* worker since the worker last had nothing
    stale. -> (rows returned more often than others added them, rows that
    others added and no Get returned); both must be 0. A Get that found
    nothing answers row 0 alone, which is not a returned row; if others
    did add row 0, a lone row 0 may be either, and row 0 is given that
    much room."""
    added = np.zeros(num_rows, np.int64)
    for ids, times in others:
        np.add.at(added, ids, times)
    lone_zero = sum(1 for ids in returned
                    if len(ids) == 1 and ids[0] == 0)
    seen = (np.bincount(np.concatenate(list(returned)), minlength=num_rows)
            if len(returned) else np.zeros(num_rows, np.int64))
    if added[0] == 0:
        seen[0] -= lone_zero
    else:
        seen[0] = max(1, seen[0] - lone_zero)
    return int(np.sum(seen > added)), int(np.sum((added > 0) & (seen == 0)))
