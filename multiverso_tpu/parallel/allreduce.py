"""Allreduce for model-average (``-ma``) mode.

Two faces, replacing the reference's two paths:

* ``device_allreduce`` — mesh-wide sum via ``psum`` under ``shard_map``.
  Replaces both ``MPI_Allreduce`` (reference mpi_net.h:148-152) and the
  hand-rolled Bruck / recursive-halving ``AllreduceEngine``
  (reference src/net/allreduce_engine.cpp:31-55): XLA picks the wire
  algorithm per message size and ICI topology, which is the same
  size-adaptive decision the engine made by hand.

* ``RendezvousAllreduce`` — in-process allreduce across worker *threads*
  (our stand-in for MPI ranks in the 1-host world, matching the semantics of
  ``MV_Aggregate`` in Test/test_allreduce.cpp:11-20: every participant
  contributes its buffer and receives the elementwise sum in place).
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from multiverso_tpu.parallel.mesh import SERVER_AXIS


def device_allreduce(x: jax.Array, mesh: Mesh, axis_name: str = SERVER_AXIS) -> jax.Array:
    """Sum ``x`` (sharded or replicated along ``axis_name``) across the mesh.

    The idiomatic form: annotate the desired output sharding and let XLA
    insert the all-reduce over ICI.
    """
    @partial(jax.shard_map, mesh=mesh, in_specs=P(axis_name),
             out_specs=P())
    def _psum(shard):
        return jax.lax.psum(shard, axis_name)

    return _psum(x)


class RendezvousAllreduce:
    """N-participant elementwise-sum rendezvous.

    Each participant thread calls ``allreduce(arr)``; all block until every
    contribution arrived, then all receive the sum. Reusable across rounds
    (generation counter), mirroring repeated ``MV_Aggregate`` calls.

    ``cross_reduce`` (optional) extends the sum beyond this process: the
    last-arriving thread applies it to the thread-summed buffer exactly once
    per round — the multihost leg of MV_Aggregate (every process's last
    thread issues the same collective; reference MPI_Allreduce,
    mpi_net.h:148-152).
    """

    def __init__(self, num_participants: int, cross_reduce=None):
        if num_participants <= 0:
            raise ValueError("num_participants must be positive")
        self.n = num_participants
        self._cross = cross_reduce
        self._lock = threading.Condition()
        self._accum: Optional[np.ndarray] = None
        self._arrived = 0
        self._generation = 0
        self._result: Optional[np.ndarray] = None
        self._error: Optional[BaseException] = None
        #: set when a participant's deadline expired mid-round: the
        #: round can never complete correctly (its contribution is in
        #: _accum but its caller has moved on), so the rendezvous
        #: BREAKS for everyone — threading.Barrier.abort semantics,
        #: fail-fast over silently skewed sums
        self._broken = False

    def allreduce(self, arr: np.ndarray) -> np.ndarray:
        arr = np.asarray(arr)
        from multiverso_tpu.failsafe import deadline as fdeadline
        with self._lock:
            if self._broken:
                fdeadline.raise_deadline(
                    "allreduce rendezvous (broken by an earlier "
                    "participant deadline)")
            gen = self._generation
            if self._accum is None:
                self._accum = arr.astype(np.float64, copy=True)
            else:
                self._accum += arr
            self._arrived += 1
            if self._arrived == self.n:
                # the round ENDS no matter what cross_reduce does — a raise
                # here must not strand the n-1 waiters or wedge future
                # rounds, so state reset + notify happen unconditionally
                result = self._accum
                error = None
                if self._cross is not None:
                    try:
                        result = np.asarray(self._cross(result))
                    except BaseException as exc:
                        error = exc
                self._result = result
                self._error = error
                self._accum = None
                self._arrived = 0
                self._generation += 1
                self._lock.notify_all()
            else:
                if not self._lock.wait_for(
                        lambda: self._generation > gen or self._broken,
                        fdeadline.timeout_or_none()):
                    # a participant never arrived: bounded by
                    # -mv_deadline_s (None = block as before). Our
                    # contribution is already in _accum and cannot be
                    # handed back, so the whole rendezvous breaks —
                    # a retry re-adding it would double-count
                    self._broken = True
                    self._lock.notify_all()
                    fdeadline.raise_deadline(
                        "allreduce rendezvous (missing participants)")
                if self._broken and self._generation <= gen:
                    fdeadline.raise_deadline(
                        "allreduce rendezvous (broken by a peer "
                        "participant deadline)")
            if self._error is not None:
                raise RuntimeError(
                    "cross-host allreduce failed") from self._error
            return self._result.astype(arr.dtype)


def jit_mean_across(params: jax.Array, mesh: Mesh, axis_name: str = SERVER_AXIS) -> jax.Array:
    """Model-average helper: mean of per-device replicas along the mesh axis
    (the `model average` training mode, reference -ma flag zoo.cpp:24,49)."""
    @partial(jax.shard_map, mesh=mesh, in_specs=P(axis_name),
             out_specs=P())
    def _pmean(shard):
        return jax.lax.pmean(shard, axis_name)

    return _pmean(params)
