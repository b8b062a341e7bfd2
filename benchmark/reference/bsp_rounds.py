"""Plain reference of BSP's semantics over a row-addressed table.

numpy float32 only: no tables, no engine, no clocks, no jax. The
guarantee it writes out is the reference server's (``src/server.cpp:60-67``,
``SyncServer``): with ``-sync=true`` all workers' i-th Get returns
identical parameters. For workers that each Add and then Get once a round
that means: the Get of round ``r`` returns, for every row it names, the
sum of ALL workers' Adds of rounds ``0..r`` and of no later round
(``Test/unittests/test_sync.cpp:25-43``, ``Test/test_array_table.cpp:13-47``:
every rank Adds then Gets, every Get equals the round's total).

The table lives over the compact space of the rows some round names: the
caller hands every id a round may name once, up front, and rows are kept
for those alone (a 9,000,000-row table of which the rounds name 640,000
rows costs the 640,000). Rounds are given in order;
``expect_get(r, ids)`` is the table after rounds ``0..r`` and before
round ``r + 1``, so it is asked between ``round(r, ...)`` and
``round(r + 1, ...)``.

It also answers what the ASYNCHRONOUS server may return to the same Get
(``async_counts``): there each worker's Adds are applied in the order it
sent them and nothing orders one worker's against another's, so a Get
holds, of every worker, its first ``k`` Adds for some ``k``, and of the
asking worker at least its own of rounds ``0..r``. BSP allows one answer
of those: ``r + 1`` of every worker. A test can so show a Get that BSP
forbids and that the asynchronous engine may give.

Departures from the reference's tests, each because this system differs:

* threads of one process stand for the reference's MPI ranks (a worker is
  an index here);
* an Add names rows of a matrix and adds a delta row to each (repeated
  ids sum), where ``test_sync.cpp`` adds to a whole ArrayTable;
* rows are added with ``+=`` on float32, the default updater.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np


class BspRounds:
    def __init__(self, num_cols: int, workers: int, named_ids):
        """``named_ids``: every row id some round may name (any shape,
        repeats allowed)."""
        self.workers = int(workers)
        self.ids = np.unique(np.asarray(named_ids).ravel())
        self.rows = np.zeros((len(self.ids), int(num_cols)), np.float32)
        #: the rounds given so far, in order: (ids, one delta a worker)
        self.rounds: List[tuple] = []

    def _slots(self, ids) -> np.ndarray:
        ids = np.asarray(ids).ravel()
        at = np.searchsorted(self.ids, ids)
        if (at >= len(self.ids)).any() or (self.ids[np.minimum(
                at, len(self.ids) - 1)] != ids).any():
            raise ValueError("a row id that was not among named_ids")
        return at

    def round(self, r: int, ids, deltas_by_worker: Sequence) -> None:
        """Round ``r``: every worker adds its delta (one row an id) to the
        rows ``ids``."""
        if r != len(self.rounds):
            raise ValueError(f"round {r} given after {len(self.rounds)} "
                             "rounds: rounds come in order, each once")
        if len(deltas_by_worker) != self.workers:
            raise ValueError("one delta a worker")
        at = self._slots(ids)
        # ``rows[at] += delta`` keeps one of a repeated id's deltas, so
        # it serves distinct ids alone (four times faster than add.at)
        distinct = len(np.unique(at)) == len(at)
        for delta in deltas_by_worker:
            delta = np.asarray(delta, np.float32).reshape(len(at), -1)
            if distinct:
                self.rows[at] += delta
            else:
                np.add.at(self.rows, at, delta)
        self.rounds.append((ids, deltas_by_worker))

    def expect_get(self, r: int, ids) -> np.ndarray:
        """What every worker's Get of round ``r`` returns for ``ids``: the
        table after rounds ``0..r`` and before round ``r + 1``."""
        if r != len(self.rounds) - 1:
            raise ValueError(f"the Get of round {r} asked after "
                             f"{len(self.rounds)} rounds")
        return self.table_rows(ids)

    def table_rows(self, ids) -> np.ndarray:
        """The rows ``ids`` after every round given so far."""
        return self.rows[self._slots(ids)].copy()

    def async_counts(self, worker: int, r: int, ids,
                     got) -> Optional[List[int]]:
        """-> how many of each worker's Adds ``got`` holds, if ``got`` is
        what the ASYNCHRONOUS server may return to ``worker``'s Get of
        round ``r`` for ``ids``, given the rounds so far (those after
        ``r`` too: a fast worker's later Adds may be in); None if no such
        counts give ``got``. BSP's one answer is ``[r + 1] * workers``.

        Takes deltas whose float32 sums are exact in any order (whole
        numbers, as the benchmark draws them): candidates are found by
        a weighted checksum in float64 and then compared entry by entry.
        """
        at = self._slots(ids)
        got = np.asarray(got, np.float32).reshape(len(at), -1)
        weights = (np.arange(got.size, dtype=np.float64) % 8191.0) + 1.0

        def checksum(rows) -> float:
            return float(np.dot(rows.ravel().astype(np.float64), weights))

        # prefix[v][k]: the rows ``ids`` if worker v's first k Adds alone
        # had been applied
        slots = [self._slots(round_ids) for round_ids, _ in self.rounds]
        scratch = np.zeros_like(self.rows)
        prefix = []
        for v in range(self.workers):
            scratch[:] = 0
            mine = [scratch[at].copy()]
            for where, (_, deltas) in zip(slots, self.rounds):
                np.add.at(scratch, where, np.asarray(
                    deltas[v], np.float32).reshape(len(where), -1))
                mine.append(scratch[at].copy())
            prefix.append(mine)
        # every worker any count, the asking worker at least r + 1
        low = [r + 1 if v == worker else 0 for v in range(self.workers)]
        sums = np.zeros((1,) * self.workers)
        for v in range(self.workers):
            shape = [1] * self.workers
            shape[v] = -1
            sums = sums + np.array(
                [checksum(p) for p in prefix[v][low[v]:]]).reshape(shape)
        for counts in np.argwhere(sums == checksum(got)):
            counts = [int(c) + low[v] for v, c in enumerate(counts)]
            total = sum(prefix[v][k] for v, k in enumerate(counts))
            if np.array_equal(total, got):
                return counts
        return None
