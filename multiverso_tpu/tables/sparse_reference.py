"""Plain reference of the SparseMatrixTable's freshness protocol.

numpy only: no tables, no engine, no jax. A float32 matrix, a bool matrix
``up_to_date[worker, row]`` and the three transitions, written as the
reference's ``UpdateAddState`` / ``UpdateGetState`` read
(src/table/sparse_matrix_table.cpp:200-259, as
``tables/sparse_matrix_table.py`` cites them): loops over workers and
rows, one bit at a time. ``tables/sparse_matrix_table.py`` must agree with
it on every interleaving of Adds and Gets; ``tests/test_sparse_table.py``
holds it to that.

Departures from the reference's text, each because this system differs:

* rows are added with ``+=`` on float32 (the default updater); the
  reference's server applies whatever updater it was created with;
* an Add's ``worker`` outside ``[0, workers)`` has no keeper and marks
  every worker (the reference's loop ``if w != worker_id`` does the same
  for an id no worker has; it is written out here);
* ``worker == -1`` on a Get returns every row and changes no bit (the
  reference's "fetch everything" branch);
* a row named twice in one Get is returned once: the loop marks it fresh
  at its first visit, so the second visit skips it;
* workers are global: in a multi-process world worker ``w`` of process
  ``p`` is ``p * workers_per_process + w``. The reference's server sees
  one flat set of worker ids too.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np


class SparseReference:
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
