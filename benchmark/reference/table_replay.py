"""Plain replay of row Adds with numpy: what a table of the default ``+=``
updater must hold after a run, on a sample of its rows.

Deltas in the table cells are small whole numbers, so float32 sums are
exact whatever order the engine applied them in, and the comparison is
bit for bit.
"""

from __future__ import annotations

import numpy as np


def expected_rows(sample: np.ndarray, cols: int, adds) -> np.ndarray:
    """Rows ``sample`` (sorted unique ids) of a zero table after ``adds``:
    an iterable of (ids, delta, times) where ``delta`` is a (len(ids),
    cols) array or a scalar added to every column, applied ``times``
    times."""
    out = np.zeros((len(sample), cols), np.float64)
    for ids, delta, times in adds:
        if not times:
            continue
        pos = np.searchsorted(sample, ids)
        pos[pos == len(sample)] = 0
        hit = sample[pos] == ids
        if np.ndim(delta) == 0:
            np.add.at(out, pos[hit], float(delta) * times)
        else:
            np.add.at(out, pos[hit],
                      np.asarray(delta, np.float64)[hit] * times)
    return out.astype(np.float32)
