"""Plain replay of a sample of rows of a table under the server's AdaGrad:
what ``moonlight-vocab-164k-2048`` must hold after a run.

The benchmark's copy of the program's ``multiverso_tpu/updaters/reference.py``
(float32 numpy, nothing of the program imported): repeated ids are summed
first, then ``g = delta / lr; h += g * g; w -= rho * g / sqrt(h + 1e-6)``
on the named rows only. A row's history depends on that row alone, so a
sample is replayed without the rest of the table; a row no step names
keeps its initial value bit for bit.

The runner's deltas are a function of the row as fetched, the step and the
column (``delta_of``), so the replay needs no payload either: it makes the
delta from its own row, as the device made it from the fetched one. A
position's repeats all carry that same delta; their sum is taken in float64
and rounded once, because the order a device sums in is not the order of
the positions. Everything else is float32, operation for operation.

``store`` rounds what is kept between steps (rows and history) through
another dtype: with bfloat16 it gives the reading that the cell's tolerance
has to refuse (``benchmark/tests/test_lm_vocab_steps.py``).
"""

from __future__ import annotations

import numpy as np

F32 = np.float32
EPS = F32(1e-6)
#: the runner's gradient: SLOPE * row + an odd number of sixteenths of
#: AMPLITUDE that turns with step, column and table and is never zero
SLOPE, AMPLITUDE = F32(0.25), F32(0.25)


def pattern(step: int, cols: int, table: int) -> np.ndarray:
    """(cols,) float32 in +-[1/16, 15/16] * AMPLITUDE, exact in float32."""
    k = (step * 7 + np.arange(cols, dtype=np.int64) * 13 + table * 5) % 16
    return (2 * k - 15).astype(F32) * F32(AMPLITUDE / 16)


def delta_of(rows: np.ndarray, step: int, table: int,
             learning_rate: float) -> np.ndarray:
    """The runner's delta for ``rows`` as fetched: ``lr * g``."""
    g = SLOPE * rows + pattern(step, rows.shape[1], table)[None, :]
    return F32(learning_rate) * g


def replay(init: np.ndarray, counts, table: int, *, learning_rate=0.01,
           rho=0.1, store=np.float32):
    """Rows ``init`` (m, cols) after the steps of ``counts``: an iterable
    of (m,) integer arrays, for each step how many positions named each of
    the rows (0: the step left the row alone). Returns (rows, history)."""
    if np.dtype(store) == F32:
        keep = lambda a: a  # noqa: E731
    else:
        keep = lambda a: a.astype(store).astype(F32)  # noqa: E731
    w = keep(np.array(init, F32))
    h = np.zeros_like(w)
    lr, rho = F32(learning_rate), F32(rho)
    for step, times in enumerate(counts):
        times = np.asarray(times)
        named = np.flatnonzero(times)
        if not len(named):
            continue
        if len(named) == len(w):
            named = slice(None)     # every row: views, not copies
        summed = delta_of(w[named], step, table, learning_rate)
        repeats = times[named]
        if (repeats != 1).any():
            summed = (summed.astype(np.float64)
                      * repeats.astype(np.float64)[:, None]).astype(F32)
        g = summed / lr
        hist = h[named] + g * g
        w[named] = keep(w[named] - rho * g / np.sqrt(hist + EPS))
        h[named] = keep(hist)
    return w, h
