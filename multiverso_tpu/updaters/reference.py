"""Plain reference for the server-side updaters' ROW semantics.

Float32 numpy, independent of ``ops/`` and ``tables/``: what a table must
hold after ``Add(row_ids, deltas, option)`` under each rule of
``updaters/base.py``, written as the arithmetic reads. Repeated ids are
summed first (the table layer's documented contract: duplicates combine by
SUM before the updater runs), then the rule is applied to the named rows
only; every other row, and every other row's state, is left as it was.

``state`` is a dict of float32 arrays: ``data`` (rows, cols), and per rule
``smooth`` (rows, cols), ``hist`` or ``backup`` (workers, rows, cols).
``new_state`` makes one; ``apply_rows`` advances it in place. Tests compare
a MatrixTable with this (tests/test_updaters_reference.py); the
benchmark keeps a copy that replays sampled rows alone
(benchmark/reference/adagrad_rows.py).
"""

from __future__ import annotations

import numpy as np

F32 = np.float32
ADAGRAD_EPS = F32(1e-6)


def combine_repeats(ids, deltas):
    """(sorted distinct ids, their summed deltas): repeats sum in float32,
    in the order they were given."""
    ids = np.asarray(ids, np.int64).ravel()
    deltas = np.asarray(deltas, F32).reshape(len(ids), -1)
    uniq, inverse = np.unique(ids, return_inverse=True)
    summed = np.zeros((len(uniq), deltas.shape[1]), F32)
    np.add.at(summed, inverse, deltas)
    return uniq, summed


def new_state(data, updater: str, num_workers: int = 1) -> dict:
    data = np.array(data, F32)
    state = {"data": data}
    if updater == "momentum":
        state["smooth"] = np.zeros_like(data)
    elif updater == "adagrad":
        state["hist"] = np.zeros((num_workers,) + data.shape, F32)
    elif updater == "dcasgd":
        state["backup"] = np.zeros((num_workers,) + data.shape, F32)
    return state


def apply_rows(updater: str, state: dict, ids, deltas, *, worker_id=0,
               momentum=0.0, learning_rate=0.01, rho=0.1,
               lambda_=0.1) -> dict:
    """One Add of ``deltas`` to rows ``ids`` under rule ``updater``
    (the keywords are ``AddOption``'s fields and defaults)."""
    ids, delta = combine_repeats(ids, deltas)
    w = state["data"]
    if updater in ("default", ""):
        w[ids] = w[ids] + delta
    elif updater == "sgd":
        w[ids] = w[ids] - delta
    elif updater == "momentum":
        m = F32(momentum)
        smooth = m * state["smooth"][ids] + (F32(1) - m) * delta
        state["smooth"][ids] = smooth
        w[ids] = w[ids] - smooth
    elif updater == "adagrad":
        g = delta / F32(learning_rate)
        h = state["hist"][worker_id, ids] + g * g
        state["hist"][worker_id, ids] = h
        w[ids] = w[ids] - F32(rho) * g / np.sqrt(h + ADAGRAD_EPS)
    elif updater == "dcasgd":
        lr = F32(learning_rate)
        lam_over_lr = F32(lambda_) / lr if lr > 0 else F32(0)
        new = w[ids] - (delta + lam_over_lr * delta * delta
                        * (w[ids] - state["backup"][worker_id, ids]))
        state["backup"][worker_id, ids] = new
        w[ids] = new
    else:
        raise ValueError(f"no reference for updater {updater!r}")
    return state
