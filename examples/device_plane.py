#!/usr/bin/env python
"""Device-plane example: a TPU-resident training loop fused with PS verbs.

The host plane (examples/logreg, examples/wordembedding) is the reference's
protocol surface — numpy in, numpy out, one host round-trip per verb. The
device plane is what the TPU build adds on top (docs/DESIGN.md §4): a
worker living on the same mesh as the store scans the table's traceable
``device_update_rows`` / ``device_gather_rows`` into its own training step,
so N parameter-server rounds compile into ONE XLA program and the weights
never leave HBM.

Here: factorize a low-rank matrix M ≈ U Vᵀ where V lives in a MatrixTable
(row-sharded over the mesh ``server`` axis) and each step gathers a row
batch, takes a gradient step, and scatters the update back — the classic
PS access pattern (cf. WordEmbedding's embedding rows), entirely on device.

Run (on whatever backend JAX selects; a CPU-only machine gets 8 host
devices from the XLA_FLAGS default below, so the table is still sharded):
    python device_plane.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

import jax

import jax.numpy as jnp
from jax import lax

import multiverso_tpu as mv
from multiverso_tpu.tables import MatrixTableOption
from multiverso_tpu.updaters import AddOption

ROWS, COLS, RANK, BATCH, STEPS, LR = 4096, 128, 8, 512, 300, 0.2


def main():
    mv.MV_Init(["-updater_type=sgd"])
    rng = np.random.default_rng(0)
    # ground truth M = A Bt; V (the PS table) must learn to reconstruct it
    A = rng.standard_normal((ROWS, RANK)).astype(np.float32)
    B = rng.standard_normal((COLS, RANK)).astype(np.float32)

    table = mv.MV_CreateTable(MatrixTableOption(
        num_rows=ROWS, num_cols=COLS, updater_type="sgd",
        initializer=lambda shape: rng.standard_normal(shape).astype(
            np.float32) * 0.01))
    server = table.server()
    opt = AddOption().as_jnp()

    # unique ids per batch: the device row ops require duplicate-free live
    # ids (the host verbs pre-combine duplicates; the traceable plane leaves
    # that to the caller — matrix_table.py module docstring)
    ids_all = np.stack([
        rng.permutation(ROWS)[:BATCH].astype(np.int32)
        for _ in range(STEPS)])
    Ad = jax.device_put(A)
    Bd = jax.device_put(B)
    ids_d = jax.device_put(ids_all)

    def step(state, ids):
        # Get: gather the batch's rows straight out of the sharded store
        rows = server.device_gather_rows(state["data"], state["aux"], ids)
        rows = rows[:, : COLS]
        target = Ad[ids] @ Bd.T                     # (BATCH, COLS) on MXU
        err = rows - target
        loss = jnp.mean(err * err)
        # Add: push the lr-scaled gradient back (sgd server: data -= delta)
        state = server.device_update_rows(state, ids, LR * err, opt)
        return state, loss

    @jax.jit
    def train(state, ids_all):
        return lax.scan(step, state, ids_all)

    state, losses = train(server.state, ids_d)
    server.state = state  # hand the trained store back to the table
    print(f"loss: {float(losses[0]):.4f} -> {float(losses[-1]):.4f} "
          f"over {STEPS} fused PS rounds on {jax.default_backend()} "
          f"({len(jax.devices())} device(s))")
    assert float(losses[-1]) < float(losses[0]) * 0.1

    # the host plane sees the device plane's work (same store)
    sample = table.GetRows(np.arange(4, dtype=np.int32))
    truth = A[:4] @ B.T
    err = np.abs(sample - truth).mean()
    print(f"host-plane readback mean abs err vs ground truth: {err:.4f}")
    mv.MV_ShutDown()


if __name__ == "__main__":
    main()
