"""On-device window training for the LogisticRegression app
(``device_plane=true``).

The reference's headline runs train LR through the PS with per-minibatch
delta pushes and periodic pulls
(Applications/LogisticRegression/src/model/ps_model.cpp:185-259); the
host-plane port mirrors that verb order, which costs per-window
host<->device round trips of the MODEL (dense: the full flat weight
vector per sync; sparse: the window's row block both ways) — the same
host<->device traffic the WordEmbedding app shed with ``-device_pairs``
(models/wordembedding/device_pairs.py).

``device_plane=true`` moves a WHOLE WINDOW into one jit'd donated XLA
program that consumes the PS tables' sharded HBM storage directly:

* dense — the ArrayTable's flat (output-major) storage reshapes to the
  weight cache in-program; the window's batches scan over it at the
  window-start weights; the per-batch lr-scaled gradients sum and apply
  once through the table's own sgd updater (``device_update``). Only
  the window's SAMPLES (X, labels, weights) are uploaded.
* sparse — the window's unique keys gather their row block from the
  MatrixTable storage (``device_gather_rows``), the batches scan over
  it with host-remapped window-local key indices, and the summed
  lr-scaled row deltas apply once (``device_update_rows``). Only the
  sample lanes (keys/values/mask, labels, weights) are uploaded.

Semantics match the host plane (parity-tested): every batch's gradient
is computed at the window-start weights, and the server rule is linear
sgd — per-batch pushes sum to the window's one application. Ragged
final windows pad with zero-lr, zero-weight batches (inert: lr scales
the delta contribution to zero and the loss metric weights to zero).
One deliberate refinement: the device plane refreshes its cache at
EVERY window start (it reads the live table), where the host plane's
reference-faithful modulo-counter sync (`_batch_count %
sync_frequency`, ps_model.cpp:172-181) drifts off window boundaries
after a ragged final window — the device cache is then FRESHER, never
staler. When epochs' batch counts divide sync_frequency the two paths
are bit-comparable (the parity tests pin that case).

Loss scalars stay ON DEVICE: ``train_window`` returns a 0-d jax array
so the driver's accumulation never forces a device->host fetch; the
periodic log line / epoch summary forces one fetch when it formats.

All three objectives ride the plane: dense (ArrayTable), sparse
(MatrixTable), and — round 5 — FTRL, whose whole window gathers the
(z, n) rows from BOTH KVTables' HBM values, scans the batches at the
window-start state, and scatters the summed negated deltas back
(``_train_ftrl``; reference ftrl_sparse_table.h + ftrl_updater.h
behavior through the KV += rule). Multi-process worlds train
COLLECTIVELY (round 4): per-process window tensors shard one global
scan axis (dense) or ride the *_parts row round (sparse), the summed
lr-scaled deltas being exactly the host plane's merged collective Add;
ragged shard streams run on filler windows (inert weight-0 batches).
FTRL's two-table program is single-process — multi-process FTRL rides
the collective host KV verbs (PSModel gates construction). Within a
process the caller owns the tables while training (the device-plane
single-writer contract).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from multiverso_tpu.parallel.mesh import next_bucket
from multiverso_tpu.updaters import AddOption
from multiverso_tpu.utils.log import CHECK

_PROGRAM_CACHE: dict = {}


class DeviceWindowTrainer:
    """Owns the window programs; constructed by PSModel when
    ``config.device_plane`` is set."""

    def __init__(self, config, model):
        self.config = config
        self.model = model
        # ftrl models keep their state in two KVTables (z, n) instead of
        # one weight table
        self.table = getattr(model, "table", None)
        self._opt = AddOption().as_jnp()
        # Device-staging budget: windows cache their uploaded sample
        # tensors on the Window objects the host-side WindowCache keeps
        # alive across epochs — those bytes are pinned in ACCELERATOR
        # memory, which the host-side cache_data_mb budget says nothing
        # about. Track them separately (weakly keyed by window, so
        # transient windows that die release their accounting and a
        # replaced attachment replaces its bytes) and stop attaching past
        # a budget derived from THIS process's device capacity (overflow
        # windows simply re-upload each epoch, like a budget-blown host
        # cache streams).
        # id-keyed (Window is unhashable); weakref.finalize releases an
        # entry when its window dies; a running total keeps the budget
        # check O(1) per attach
        self._staged_live: dict = {}
        self._staged_total = 0
        self._staged_budget = self._device_staging_budget()

    @property
    def _staged_bytes(self) -> int:
        """Per-device bytes currently pinned by LIVE window attachments."""
        return self._staged_total

    def _release_staged(self, wid: int) -> None:
        """Drop a window's accounting entry (finalizer + decline path);
        idempotent — a window may register two finalizers across a
        release/re-attach cycle."""
        n = self._staged_live.pop(wid, None)
        if n:
            self._staged_total -= n

    @staticmethod
    def _device_staging_budget() -> int:
        """Per-device bytes the epoch cache may pin: a quarter of this
        process's device memory. The CPU backend reports no memory
        statistics and gets a conservative 1GB; a chip that fails to
        report them is an error, not a 1GB chip. local_devices: in a
        multi-process world jax.devices()[0] may be another process's
        non-addressable chip."""
        import jax
        stats = jax.local_devices()[0].memory_stats()
        if stats is None and jax.default_backend() == "cpu":
            return 1 << 30
        return max(int(stats["bytes_limit"]) // 4, 64 << 20)

    @staticmethod
    def _per_device_bytes(a) -> int:
        """Bytes ONE device holds of ``a``: global arrays spread nbytes
        over their device set (the budget is per-device HBM), replicated
        arrays cost full size per device."""
        nbytes = getattr(a, "nbytes", 0)
        try:
            if not a.is_fully_replicated:
                return nbytes // max(1, len(a.sharding.device_set))
        except Exception:
            pass
        return nbytes

    def _attach_staged(self, window, attr: str, staged: tuple) -> None:
        """Pin ``staged`` on the window for epoch replay only while the
        device-staging budget holds; past it the window trains from the
        local arrays and re-uploads next epoch."""
        import weakref
        nbytes = sum(self._per_device_bytes(a) for a in staged[1:])
        wid = id(window)
        prev = self._staged_live.get(wid, 0)
        if self._staged_total - prev + nbytes <= self._staged_budget:
            setattr(window, attr, staged)
            if wid not in self._staged_live:
                weakref.finalize(window, self._release_staged, wid)
            self._staged_total += nbytes - prev
            self._staged_live[wid] = nbytes
        elif prev:
            # declined REPLACEMENT (meta drifted, e.g. the shared filler
            # window's per-slot K): the stale attachment is unusable dead
            # weight — actually release it so 'overflow re-uploads' holds
            self._release_staged(wid)
            if hasattr(window, attr):
                delattr(window, attr)

    # -- host-side window staging -------------------------------------------

    def train_window(self, window, agreed=None):
        """One Window as one donated program dispatch; returns the summed
        window loss as a DEVICE scalar (fetch-on-format).

        Multi-process (round 4): COLLECTIVE, lockstep windows (the
        driver's pop protocol feeds finished ranks empty filler windows).
        Per-process window tensors become shards of batch-sharded global
        arrays (place_parts); the linear per-batch deltas sum across all
        processes' batches inside the traced program — exactly the host
        plane's collective merged Add — and the identical update applies
        everywhere. ``agreed`` carries the driver-allgathered sparse
        statics (shared K and key bucket)."""
        cfg = self.config
        from multiverso_tpu.parallel import multihost
        from multiverso_tpu.parallel.mesh import (local_device_count,
                                                  pad_to_multiple)
        nb = max(1, cfg.sync_frequency)
        nproc = multihost.process_count()
        if nproc > 1:
            # multi-process windows are COLLECTIVE with lockstep pops:
            # the guard fails fast when a caller bypasses the driver's
            # pop protocol (LogReg._train pop_window), whose absence
            # would otherwise surface as a silent distributed hang on
            # ragged shard streams
            CHECK(agreed is not None,
                  "multi-process device_plane windows must come through "
                  "the collective pop protocol (LogReg._train attaches "
                  "the allgathered statics); direct train_window calls "
                  "would hang on ragged shard streams")
            # the stacked batch axis shards P(server) over the WHOLE
            # mesh: pad the per-process batch count to a local-device
            # multiple with inert (weight 0, lr 0) batches
            mesh = self.table.server()._zoo.mesh_ctx.mesh
            nb = pad_to_multiple(nb, local_device_count(mesh))
        batches = window.batches
        # per-batch decayed lr, ticking ONLY real batches (pad batches get
        # lr 0 -> their whole delta contribution is scaled out)
        lrs = np.zeros(nb, np.float32)
        for i in range(len(batches)):
            lrs[i] = self.model.updater.learning_rate()
            self.model.updater.tick()
        self.model._batch_count += len(batches)
        self.model.compute_count += len(batches)
        if self.model.ftrl:
            CHECK(nproc <= 1, "ftrl device_plane is single-process "
                  "(multi-process worlds ride the collective host verbs "
                  "— PSModel gates construction)")
            return self._train_ftrl(window, nb)
        if cfg.sparse:
            return self._train_sparse(window, nb, lrs, agreed)
        return self._train_dense(window, nb, lrs)

    def _train_dense(self, window, nb: int, lrs: np.ndarray):
        import jax.numpy as jnp

        from multiverso_tpu.parallel import multihost
        from multiverso_tpu.parallel.mesh import place_parts
        cfg = self.config
        nproc = multihost.process_count()
        srv = self.table.server()
        staged = getattr(window, "_staged_dense", None)
        if staged is None or staged[0] != (nb, nproc):
            B = cfg.minibatch_size
            cdt = jnp.dtype(cfg.compute_type)
            X = np.zeros((nb, B, cfg.input_size), cdt)
            labels = np.zeros((nb, B), np.int32)
            weights = np.zeros((nb, B), np.float32)
            for i, b in enumerate(window.batches):
                X[i] = b.dense
                labels[i] = b.labels
                weights[i] = b.weights
            if nproc > 1:
                # every process's window batches stack into one
                # batch-sharded scan axis: the summed lr-scaled grads ARE
                # the collective merged Add (linear server rule)
                mesh = srv._zoo.mesh_ctx.mesh
                parts = (place_parts(mesh, X, nproc),
                         place_parts(mesh, labels, nproc),
                         place_parts(mesh, weights, nproc))
            else:
                parts = (jnp.asarray(X), jnp.asarray(labels),
                         jnp.asarray(weights))
            # DEVICE-staged: with the epoch cache replaying windows, later
            # epochs skip the host staging AND the upload (lrs re-upload
            # per call — the decay schedule moves); attachment is bounded
            # by the device-staging budget (_attach_staged)
            staged = ((nb, nproc),) + parts
            self._attach_staged(window, "_staged_dense", staged)
        if nproc > 1:
            lrs_g = place_parts(srv._zoo.mesh_ctx.mesh, lrs, nproc)
            n_total = nproc * nb
        else:
            lrs_g = jnp.asarray(lrs)
            n_total = nb
        program = self._dense_program(n_total)
        new_state, loss = program(srv.device_state(), staged[1], staged[2],
                                  staged[3], lrs_g)
        srv.device_set_state(new_state)
        loss.copy_to_host_async()   # the lagged epoch log finds it landed
        return loss

    def _train_sparse(self, window, nb: int, lrs: np.ndarray, agreed=None):
        import jax.numpy as jnp

        from multiverso_tpu.parallel import multihost
        from multiverso_tpu.parallel.mesh import (local_device_count,
                                                  parts_bucket, place_parts)
        cfg = self.config
        B = cfg.minibatch_size
        srv = self.table.server()
        nproc = multihost.process_count()
        keys = window.keys                       # unique, sorted (np.unique)
        if nproc > 1:
            if agreed is None:
                parts = multihost.host_allgather_objects_capped(
                    (max((b.keys.shape[1] for b in window.batches),
                         default=1), len(keys)), "lr_dp_agreed")
                agreed = (max(p[0] for p in parts),
                          max(max(p[1] for p in parts), 1))
            K = agreed[0]
            bucket = parts_bucket(agreed[1], local_device_count(srv._mesh))
            # a filler/empty window still joins the collective round: one
            # real key (row 0) with all-zero deltas is inert
            if keys.size == 0:
                keys = np.zeros(1, np.int64)
        else:
            if keys.size == 0:
                return jnp.float32(0.0)
            bucket = next_bucket(len(keys))
            K = max(b.keys.shape[1] for b in window.batches)
        staged = getattr(window, "_staged_sparse", None)
        if staged is None or staged[0] != (nb, K, bucket, nproc):
            # window-local remap + K-lane padding on the host (the
            # reader's batches already pad ragged samples with key 0 /
            # mask 0; the window-level K extension uses the same
            # convention so the device program sees exactly the host
            # path's lane set). Multi-process, the remapped indices
            # address THIS process's slice of the global gathered row
            # block: lane = rank*bucket + local_index.
            rank = multihost.process_index()
            base = rank * bucket if nproc > 1 else 0
            bkeys = np.zeros((nb, B, K), np.int32)
            values = np.zeros((nb, B, K), np.float32)
            mask = np.zeros((nb, B, K), np.float32)
            labels = np.zeros((nb, B), np.int32)
            weights = np.zeros((nb, B), np.float32)
            for i, b in enumerate(window.batches):
                kb = b.keys.shape[1]
                bkeys[i, :, :kb] = base + np.searchsorted(keys, b.keys)
                bkeys[i, :, kb:] = base + np.searchsorted(keys, 0)
                values[i, :, :kb] = b.values
                mask[i, :, :kb] = b.mask
                labels[i] = b.labels
                weights[i] = b.weights
            if nproc > 1:
                gids = srv.device_place_batch(keys.astype(np.int32),
                                              bucket=bucket)
                mesh = srv._mesh
                arrs = (gids, place_parts(mesh, bkeys, nproc),
                        place_parts(mesh, values, nproc),
                        place_parts(mesh, mask, nproc),
                        place_parts(mesh, labels, nproc),
                        place_parts(mesh, weights, nproc))
            else:
                ids = np.full(bucket, -1, np.int32)
                ids[: len(keys)] = keys.astype(np.int32)
                arrs = (jnp.asarray(ids), jnp.asarray(bkeys),
                        jnp.asarray(values), jnp.asarray(mask),
                        jnp.asarray(labels), jnp.asarray(weights))
            staged = ((nb, K, bucket, nproc),) + arrs
            self._attach_staged(window, "_staged_sparse", staged)
        if nproc > 1:
            lrs_g = place_parts(srv._mesh, lrs, nproc)
            nb_total = nproc * nb
        else:
            lrs_g = jnp.asarray(lrs)
            nb_total = nb
        program = self._sparse_program(nb_total, B, K,
                                       bucket * max(nproc, 1), nproc > 1)
        state = dict(srv.state)
        new_state, loss = program(state, staged[1], staged[2], staged[3],
                                  staged[4], staged[5], staged[6], lrs_g)
        srv.state = new_state
        loss.copy_to_host_async()   # the lagged epoch log finds it landed
        return loss

    def _train_ftrl(self, window, nb: int):
        """One FTRL window on device (VERDICT r4 #4): gather the window
        keys' (z, n) rows from BOTH KVTables' HBM values, scan the
        batches at the window-start state (exactly the host path's
        convention, model.py _train_window_ftrl), scatter the summed
        negated deltas back — the closed-form z/n update never leaves
        HBM. Matches reference
        Applications/LogisticRegression/src/util/ftrl_sparse_table.h:1-90
        + updater/ftrl_updater.h behavior through the KV (+=) rule."""
        import jax.numpy as jnp
        cfg = self.config
        B = cfg.minibatch_size
        model = self.model
        zsrv = model.z_table.server()
        nsrv = model.n_table.server()
        keys = window.keys
        if keys.size == 0:
            return jnp.float32(0.0)
        out = cfg.output_size
        R = len(keys)
        flat = model._flat_keys(keys)               # (R*out,) unique
        K = max(b.keys.shape[1] for b in window.batches)
        # Slot vectors stage WITH the window (the key covers the table
        # capacities: growth moves the pad slot, so stale uploads
        # re-stage) — the per-window slot upload AND the O(R*out) host
        # resolution are real wall time, so a staged hit
        # skips BOTH: the window's keys were created at staging time and
        # KV slots are append-only, so unchanged capacities mean
        # unchanged slots.
        staged = getattr(window, "_staged_ftrl", None)
        if staged is None or staged[0] != (nb, K, R, zsrv.capacity,
                                           nsrv.capacity):
            # resolve BEFORE taking device_values (create may grow and
            # swap the backing arrays — kv_table.py device-plane
            # contract); re-read capacities after (growth during create)
            zslots = zsrv.device_slots(flat, create=True)
            nslots = nsrv.device_slots(flat, create=True)
            skey = (nb, K, R, zsrv.capacity, nsrv.capacity)
            bkeys = np.zeros((nb, B, K), np.int32)
            values = np.zeros((nb, B, K), np.float32)
            mask = np.zeros((nb, B, K), np.float32)
            labels = np.zeros((nb, B), np.int32)
            weights = np.zeros((nb, B), np.float32)
            for i, b in enumerate(window.batches):
                kb = b.keys.shape[1]
                bkeys[i, :, :kb] = np.searchsorted(keys, b.keys)
                values[i, :, :kb] = b.values
                mask[i, :, :kb] = b.mask
                labels[i] = b.labels
                weights[i] = b.weights
            staged = (skey, jnp.asarray(zslots), jnp.asarray(nslots),
                      jnp.asarray(bkeys), jnp.asarray(values),
                      jnp.asarray(mask), jnp.asarray(labels),
                      jnp.asarray(weights))
            self._attach_staged(window, "_staged_ftrl", staged)
        program = self._ftrl_program(nb, B, K, R, staged[1].shape[0],
                                     staged[2].shape[0], zsrv.capacity,
                                     nsrv.capacity)
        new_z, new_n, loss = program(
            zsrv.device_values(), nsrv.device_values(), *staged[1:])
        zsrv.device_set_values(new_z)
        nsrv.device_set_values(new_n)
        loss.copy_to_host_async()   # the lagged epoch log finds it landed
        return loss

    # -- the window programs -------------------------------------------------

    def _ftrl_program(self, nb: int, B: int, K: int, R: int,
                      z_bucket: int, n_bucket: int, z_cap: int,
                      n_cap: int):
        cfg = self.config
        key = ("lr_ftrl", nb, B, K, R, z_bucket, n_bucket, z_cap, n_cap,
               cfg.output_size, cfg.alpha, cfg.beta, cfg.lambda1,
               cfg.lambda2)
        if key in _PROGRAM_CACHE:
            return _PROGRAM_CACHE[key]
        import jax
        import jax.numpy as jnp
        from jax import lax

        grad_fn = self.model._ftrl_grad
        out = cfg.output_size

        def program(z_vals, n_vals, zslots, nslots, bkeys, values, mask,
                    labels, weights):
            z_rows = z_vals[zslots][: R * out].reshape(R, out)
            n_rows = n_vals[nslots][: R * out].reshape(R, out)

            def body(acc, x):
                k, v, m, lab, wt = x
                dz, dn, loss = grad_fn(z_rows, n_rows, k, v, m, lab, wt)
                return (acc[0] + dz, acc[1] + dn), loss

            (dz_acc, dn_acc), losses = lax.scan(
                body, (jnp.zeros((R, out), jnp.float32),
                       jnp.zeros((R, out), jnp.float32)),
                (bkeys, values, mask, labels, weights))
            # the host path pushes the NEGATED accumulators through the
            # KV += rule (model.py:366-369); pad slot lanes carry zero
            z_delta = jnp.zeros((z_bucket,), jnp.float32).at[
                : R * out].set(-dz_acc.reshape(-1))
            n_delta = jnp.zeros((n_bucket,), jnp.float32).at[
                : R * out].set(-dn_acc.reshape(-1))
            new_z = z_vals.at[zslots].add(z_delta)
            new_n = n_vals.at[nslots].add(n_delta)
            return new_z, new_n, jnp.sum(losses)

        compiled = jax.jit(program, donate_argnums=(0, 1))
        _PROGRAM_CACHE[key] = compiled
        return compiled

    def _dense_program(self, nb: int):
        # structural key (NOT table identity): a fresh world with the same
        # table geometry reuses the compiled program — the traced closure
        # bakes in only shapes and updater constants, state rides as an
        # argument (the device_pairs._PROGRAM_CACHE convention)
        cfg = self.config
        srv = self.table.server()
        key = ("lr_dense", nb, cfg.minibatch_size, cfg.compute_type,
               cfg.input_size, cfg.output_size, srv.padded,
               type(srv.updater).__name__, cfg.objective_type,
               cfg.regular_type, cfg.regular_coef)
        if key in _PROGRAM_CACHE:
            return _PROGRAM_CACHE[key]
        import jax
        import jax.numpy as jnp
        from jax import lax

        srv = self.table.server()
        grad_fn = self.model._dense_grad
        n_in, n_out = cfg.input_size, cfg.output_size
        opt = self._opt

        def program(state, X, labels, weights, lrs):
            # the ArrayTable stores the flat OUTPUT-MAJOR weights
            # (reference key layout); the cache view is (in, out)
            W = state["data"][: n_in * n_out].reshape(n_out, n_in).T

            def body(acc, x):
                Xb, lab, wt, lr = x
                grad, loss = grad_fn(W, Xb, lab, wt)
                return acc + lr * grad, loss

            delta, losses = lax.scan(
                body, jnp.zeros((n_in, n_out), jnp.float32),
                (X, labels, weights, lrs))
            padded = jnp.zeros_like(state["data"]).at[: n_in * n_out].set(
                delta.T.reshape(-1))
            return srv.device_update(state, padded, opt), jnp.sum(losses)

        compiled = jax.jit(program, donate_argnums=(0,))
        _PROGRAM_CACHE[key] = compiled
        return compiled

    def _sparse_program(self, nb: int, B: int, K: int, bucket: int,
                        parts: bool = False):
        """``bucket`` is the GLOBAL gathered-row count (nproc * per-rank
        bucket when ``parts``); ``parts`` switches the gather/update to
        the collective *_parts verbs (cross-process duplicate keys
        combine by sum inside the trace)."""
        cfg = self.config
        srv = self.table.server()
        key = ("lr_sparse", nb, B, K, bucket, parts, cfg.output_size,
               srv.block_rows, srv.store_cols, srv.num_rows,
               type(srv.updater).__name__, cfg.objective_type,
               cfg.regular_type, cfg.regular_coef)
        if key in _PROGRAM_CACHE:
            return _PROGRAM_CACHE[key]
        import jax
        import jax.numpy as jnp
        from jax import lax

        srv = self.table.server()
        grad_fn = self.model._sparse_grad
        n_out = cfg.output_size
        opt = self._opt

        def program(state, ids, bkeys, values, mask, labels, weights, lrs):
            if parts:
                W_rows = srv.device_gather_rows_parts(
                    state["data"], state["aux"], ids)  # (nproc*bucket, out)
            else:
                W_rows = srv.device_gather_rows(state["data"], state["aux"],
                                                ids)   # (bucket, out)

            def body(acc, x):
                k, v, m, lab, wt, lr = x
                grad, loss = grad_fn(W_rows, k, v, m, lab, wt)
                return acc + lr * grad, loss

            delta, losses = lax.scan(
                body, jnp.zeros((bucket, n_out), jnp.float32),
                (bkeys, values, mask, labels, weights, lrs))
            if parts:
                return (srv.device_update_rows_parts(state, ids, delta,
                                                     opt), jnp.sum(losses))
            return (srv.device_update_rows(state, ids, delta, opt),
                    jnp.sum(losses))

        compiled = jax.jit(program, donate_argnums=(0,))
        _PROGRAM_CACHE[key] = compiled
        return compiled
