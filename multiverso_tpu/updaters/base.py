"""Server-side updaters as jit-able pure functions on table shards.

Behavioral equivalent of the reference updater stack
(include/multiverso/updater/updater.h + sgd/momentum/adagrad headers,
src/updater/updater.cpp): the server applies a pluggable update rule to its
shard for every incoming Add, parameterized per-message by an ``AddOption``
(worker_id, momentum, learning_rate, rho, lambda — updater.h:10-70).

TPU design: each updater is a *pure elementwise transform*
``update(data, aux, delta, opt) -> (data, aux)`` that the table layer jits
over its sharded storage (donated, so HBM is updated in place). Option
scalars are traced ``jnp`` values, not static args — changing lr per Add
does NOT retrigger compilation (SURVEY.md §7 "option-carrying updates").
Per-worker state (AdaGrad's historic g², reference adagrad_updater.h:19,26;
the leaves an updater names in ``per_worker``) is ROW-SHAPED like the data
and sharded along the same server axis: a leaf of ``num_workers *
data.shape[0]`` rows in which every shard holds its workers' blocks one
after another (``worker_block`` / ``set_worker_block``). ``update`` never
sees that layout: the caller hands it ONE worker's state, shaped like the
data it updates — the rows of the Add on the row path, that worker's block
in a whole-table Add (``update_worker``) — and stores what it returns.

Updater selection is keyed by the ``updater_type`` flag exactly like the
reference factory (src/updater/updater.cpp:46-57).

Deviation note (intentional): the reference AdaGrad has two evident defects —
``auto g_sqr_data_ = historic_g_sqr_.at(...)`` *copies* the history so it
never persists (adagrad_updater.h:26), and the history is *decremented* by
delta² so sqrt sees negative numbers (adagrad_updater.h:28-30). We implement
the evident intent: ``hist += (delta/lr)²; data -= rho * (delta/lr) /
sqrt(hist + e)``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from multiverso_tpu.utils.configure import MV_DEFINE_string

MV_DEFINE_string("updater_type", "default", "server updater rule")


@dataclass
class AddOption:
    """Per-Add parameters riding along with the delta
    (reference updater.h:10-70; defaults match AddOption())."""

    worker_id: int = 0
    momentum: float = 0.0
    learning_rate: float = 0.01
    rho: float = 0.1
    lambda_: float = 0.1

    def as_jnp(self) -> Dict[str, jax.Array]:
        """Traced scalars handed to the jit'd updater (no retrace on change)."""
        return {
            "worker_id": jnp.asarray(self.worker_id, jnp.int32),
            "momentum": jnp.asarray(self.momentum, jnp.float32),
            "learning_rate": jnp.asarray(self.learning_rate, jnp.float32),
            "rho": jnp.asarray(self.rho, jnp.float32),
            "lambda_": jnp.asarray(self.lambda_, jnp.float32),
        }


@dataclass
class GetOption:
    """Per-Get parameters (reference updater.h:72-110): the requesting
    worker's id — needed by per-worker server state such as the
    SparseMatrixTable dirty-row bits."""

    worker_id: int = 0


class Updater:
    """Base = plain accumulation: ``data += delta``
    (reference src/updater/updater.cpp:21-29; OpenMP there, XLA here)."""

    name = "default"
    #: True when the rule is a pure elementwise fn of (data, delta) — no aux,
    #: no opt, identity on zero delta — so the row path may run it as
    #: ``combine`` inside ops.update_rows (no aux round). Defaults
    #: to False so a subclass overriding ``update()`` is never silently
    #: replaced by the inherited '+=' combine on the row path; opt in by
    #: setting True AND overriding ``combine`` to match ``update``.
    fusable = False
    #: when the rule is LINEAR — update(data, delta) == data +
    #: combine_scale * delta, with combine_scale a CONSTANT of the class —
    #: merged engine Adds may apply a window's concatenated batches as one
    #: duplicate-safe scatter-add (matrix_table.ProcessAddRun). Linearity
    #: is a CONTRACT: the rule must ignore AddOption scalars entirely (the
    #: merge applies one default option to the whole window; a subclass
    #: whose update reads opt must leave combine_scale = None).
    #: None = not linear, never merge.
    combine_scale = None
    #: names of the aux leaves that are PER-WORKER state (``worker_rows``
    #: makes them); every other leaf is shared state, shaped like data
    per_worker = ()

    def init_aux(self, shape, dtype, num_workers: int) -> Dict[str, Any]:
        """Aux state pytree: shared leaves shaped like data, per-worker
        leaves (``per_worker``) made by ``worker_rows``."""
        return {}

    def is_per_worker(self, keypath: str) -> bool:
        """Whether the aux leaf at ``keypath`` (its key, or the key path
        ``jax.tree_util.keystr`` prints: "['hist']") is per-worker."""
        return keypath.strip("[']") in self.per_worker

    def combine(self, rows: jax.Array, deltas: jax.Array) -> jax.Array:
        """The fusable elementwise rule (only called when ``fusable``)."""
        return rows + deltas

    def update(self, data: jax.Array, aux: Dict[str, Any], delta: jax.Array,
               opt: Dict[str, jax.Array]):
        """The rule, elementwise over ``data``. Every aux leaf arrives
        shaped like ``data``: a per-worker leaf is the state of the ONE
        worker that sent the Add (module docstring)."""
        return data + delta, aux

    def update_worker(self, data, aux, delta, opt, num_workers: int,
                      num_shards: int):
        """A WHOLE-TABLE Add over the stored aux: ``update`` on the
        sending worker's blocks of the per-worker leaves (``opt
        ["worker_id"]``, traced), the other workers' left as they were."""
        wid = opt["worker_id"]
        mine = {k: worker_block(v, wid, num_workers, num_shards)
                if self.is_per_worker(k) else v for k, v in aux.items()}
        data, new = self.update(data, mine, delta, opt)
        return data, {k: set_worker_block(aux[k], v, wid, num_workers,
                                          num_shards)
                      if self.is_per_worker(k) else v for k, v in new.items()}

    def access(self, data: jax.Array, aux: Dict[str, Any],
               opt: Dict[str, jax.Array]) -> jax.Array:
        """Get path — identity for every reference updater (memcpy,
        updater.cpp:32)."""
        return data


def worker_rows(shape, dtype, num_workers: int) -> jax.Array:
    """Zeroed per-worker state for data of ``shape``: ``num_workers *
    shape[0]`` rows shaped like the data's. Sharded on the row axis like
    the data, a shard holds ITS rows of every worker, block after block:
    worker ``w``'s row ``r`` of a shard is that shard's row ``w *
    shard_rows + r``."""
    return jnp.zeros((num_workers * shape[0],) + tuple(shape[1:]), dtype)


def _shard_blocks(leaf, num_workers: int, num_shards: int):
    return leaf.reshape((num_shards, num_workers, -1) + leaf.shape[1:])


def worker_block(leaf, wid, num_workers: int, num_shards: int):
    """One worker's state out of a ``worker_rows`` leaf, whole table:
    shaped like the data (``wid`` may be traced)."""
    block = lax.dynamic_index_in_dim(
        _shard_blocks(leaf, num_workers, num_shards), wid, axis=1,
        keepdims=False)
    return block.reshape((-1,) + leaf.shape[1:])


def unstack_workers(leaf, num_workers: int, num_shards: int):
    """A whole ``worker_rows`` leaf (a host or a device array) ->
    ``(num_workers,) + the data's shape``: the checkpoint's form."""
    blocks = _shard_blocks(leaf, num_workers, num_shards)
    return blocks.swapaxes(0, 1).reshape(
        (num_workers, -1) + leaf.shape[1:])


def stack_workers(per_worker, num_shards: int):
    """The inverse: ``(num_workers,) + the data's shape`` -> the
    ``worker_rows`` layout."""
    blocks = per_worker.reshape(
        (per_worker.shape[0], num_shards, -1) + per_worker.shape[2:])
    return blocks.swapaxes(0, 1).reshape((-1,) + per_worker.shape[2:])


def set_worker_block(leaf, block, wid, num_workers: int, num_shards: int):
    """``leaf`` with one worker's whole-table state replaced."""
    block = block.reshape((num_shards, 1, -1) + leaf.shape[1:])
    return lax.dynamic_update_slice_in_dim(
        _shard_blocks(leaf, num_workers, num_shards), block, wid,
        axis=1).reshape(leaf.shape)


class AddUpdater(Updater):
    name = "default"
    fusable = True  # combine (inherited '+=') IS update
    combine_scale = 1.0


class SGDUpdater(Updater):
    """``data -= delta`` — the client sends lr-scaled gradients
    (reference sgd_updater.h:15-19)."""

    name = "sgd"
    fusable = True
    combine_scale = -1.0

    def combine(self, rows, deltas):
        return rows - deltas

    def update(self, data, aux, delta, opt):
        return data - delta, aux


class MomentumUpdater(Updater):
    """Smoothed-gradient descent (reference momentum_updater.h:18-26):
    ``smooth = m * smooth + (1-m) * delta; data -= smooth``.
    One shared smooth buffer (not per worker) like the reference."""

    name = "momentum"

    def init_aux(self, shape, dtype, num_workers):
        return {"smooth": jnp.zeros(shape, dtype)}

    def update(self, data, aux, delta, opt):
        m = opt["momentum"].astype(data.dtype)
        smooth = m * aux["smooth"] + (1 - m) * delta
        return data - smooth, {"smooth": smooth}


class AdaGradUpdater(Updater):
    """Per-worker AdaGrad (reference adagrad_updater.h:15-58, intent — see
    module deviation note): the server keeps one historic-g² buffer per
    worker; the per-Add worker_id selects which history to advance."""

    name = "adagrad"
    eps = 1e-6
    per_worker = ("hist",)

    def init_aux(self, shape, dtype, num_workers):
        return {"hist": worker_rows(shape, dtype, num_workers)}

    def update(self, data, aux, delta, opt):
        lr = opt["learning_rate"].astype(data.dtype)
        rho = opt["rho"].astype(data.dtype)
        grad = delta / lr
        hist = aux["hist"] + grad * grad
        data = data - rho * grad / jnp.sqrt(hist + self.eps)
        return data, {"hist": hist}


class DCASGDUpdater(Updater):
    """Delay-compensated ASGD (reference hook: src/updater/updater.cpp:2-12
    selects a DCASGD updater behind ``ENABLE_DCASGD``, but the headers are
    absent from the snapshot — ``include/multiverso/updater/dcasgd/`` is
    empty, SURVEY.md §2b — so this implements the published algorithm the
    hook names: Zheng et al., "Asynchronous SGD with Delay Compensation").

    The server keeps one parameter *backup* per worker — the model that
    worker last saw. An Add from worker m carries ``delta = lr * g`` (SGD
    client convention, sgd_updater.h:15-19) and applies

        w -= delta + (lambda / lr) * delta^2 * (w - backup[m])
           = lr * (g + lambda * g*g*(w - backup[m]))

    i.e. a first-order correction of the stale gradient toward the current
    parameters, then refreshes ``backup[m] = w``. The backup starts at zero
    (aux init has no access to initial data); the compensation term is a
    correction, so the first push per worker is plain SGD-magnitude off and
    self-corrects immediately after. Selected by ``-updater_type=dcasgd``
    (the reference gates the same choice at compile time)."""

    name = "dcasgd"
    per_worker = ("backup",)

    def init_aux(self, shape, dtype, num_workers):
        return {"backup": worker_rows(shape, dtype, num_workers)}

    def update(self, data, aux, delta, opt):
        lr = opt["learning_rate"].astype(data.dtype)
        lam = opt["lambda_"].astype(data.dtype)
        bak = aux["backup"]
        # lr rides in traced (no retrace on change), so a zero can't raise
        # here — degrade the compensation to plain SGD instead of poisoning
        # the table with inf/NaN (the native runtime applies the same
        # degrade, store.cc DcasgdUpdaterC)
        lam_over_lr = jnp.where(lr > 0, lam / jnp.maximum(lr, 1e-30), 0.0)
        new = data - (delta + lam_over_lr * delta * delta * (data - bak))
        return new, {"backup": new}


_REGISTRY = {
    "default": AddUpdater,
    "": AddUpdater,
    "sgd": SGDUpdater,
    "momentum": MomentumUpdater,
    "adagrad": AdaGradUpdater,
    "dcasgd": DCASGDUpdater,
}


def CreateUpdater(updater_type: str | None = None) -> Updater:
    """Factory keyed by the ``updater_type`` flag
    (reference src/updater/updater.cpp:46-57; unknown -> default)."""
    if updater_type is None:
        from multiverso_tpu.utils.configure import GetFlag
        updater_type = GetFlag("updater_type")
    cls = _REGISTRY.get(updater_type, AddUpdater)
    return cls()
