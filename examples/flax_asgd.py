#!/usr/bin/env python
"""Multi-worker ASGD training of a flax model through the parameter server.

The reference shipped Theano/Lasagne/Keras adapters for exactly this
pattern (reference theano_ext/lasagne_ext/param_manager.py,
keras_ext/callbacks.py:8-39, benchmark: binding/python/docs/BENCHMARK.md
ResNet-32 ASGD rows). The modern JAX-native stack is flax.linen + optax;
the adapter is the same ``JaxParamManager`` delta-sync (pytrees flatten
into ONE ArrayTable vector) plus ``SyncCallback`` — the Keras-callback
equivalent that syncs every ``freq`` batches.

Each worker owns a private model replica and a disjoint data shard; every
sync it pushes (current - last_synced) and pulls the merged parameters —
the reference's delta trick (param_manager.py:67-82). The replicas
converge to one shared model that fits the whole dataset.

Run:  python flax_asgd.py
"""

import threading

import numpy as np

import jax

import flax.linen as nn
import jax.numpy as jnp
import optax

import multiverso_tpu as mv
from multiverso_tpu.binding import ArrayTableHandler
from multiverso_tpu.binding.param_manager import (JaxParamManager,
                                                  SyncCallback, _flatten)

WORKERS, EPOCHS, BATCH, SYNC_FREQ = 2, 8, 64, 4
FEATURES, CLASSES, N = 20, 3, 3000


class MLP(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Dense(64)(x))
        return nn.Dense(CLASSES)(x)


def init_params():
    # identical init on every worker (the master's push wins; others
    # contribute zeros — the binding's master-initializes convention)
    return MLP().init(jax.random.PRNGKey(7), jnp.zeros((1, FEATURES)))


def main():
    dev = jax.devices()
    print(f"running on {len(dev)} x {dev[0].platform} ({dev[0].device_kind})")
    rng = np.random.default_rng(0)
    centers = rng.standard_normal((CLASSES, FEATURES)).astype(np.float32) * 2
    y = rng.integers(0, CLASSES, N)
    X = centers[y] + rng.standard_normal((N, FEATURES)).astype(np.float32)

    mv.MV_Init([f"-num_workers={WORKERS}"])

    @jax.jit
    def train_step(params, opt_state, xb, yb):
        def loss_fn(p):
            logits = MLP().apply(p, xb)
            one_hot = jax.nn.one_hot(yb, CLASSES)
            return optax.softmax_cross_entropy(logits, one_hot).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    @jax.jit
    def accuracy(params, xb, yb):
        return (MLP().apply(params, xb).argmax(-1) == yb).mean()

    tx = optax.sgd(0.05)

    # ONE shared table for all in-process workers, sized from the pytree
    template = init_params()
    init_vec = _flatten([np.asarray(leaf).ravel()
                         for leaf in jax.tree.leaves(template)])
    shared = ArrayTableHandler(init_vec.size, init_value=init_vec)

    final_acc = {}

    def worker(wid):
        with mv.MV_WorkerContext(wid):
            wrng = np.random.default_rng(wid)  # Generators aren't thread-safe
            mgr = JaxParamManager(init_params(), table=shared)
            params = mgr.params()
            opt_state = tx.init(params)
            cb = SyncCallback(mgr, freq=SYNC_FREQ)
            shard = slice(wid * N // WORKERS, (wid + 1) * N // WORKERS)
            Xs, ys = X[shard], y[shard]
            for _ in range(EPOCHS):
                perm = wrng.permutation(len(Xs))
                for start in range(0, len(Xs), BATCH):
                    idx = perm[start:start + BATCH]
                    params, opt_state, _ = train_step(
                        params, opt_state, Xs[idx], ys[idx])
                    mgr.update(params)          # hand progress to the mgr
                    cb.on_batch_end()           # delta-sync every SYNC_FREQ
                    params = mgr.params()       # continue from merged state
            cb.on_train_end()                   # final flush + pull
            params = mgr.params()
            final_acc[wid] = float(accuracy(params, X, y))

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(WORKERS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    mv.MV_ShutDown()

    accs = [final_acc[w] for w in range(WORKERS)]
    print(f"per-worker accuracy on the FULL dataset: "
          f"{', '.join(f'{a:.3f}' for a in accs)}")
    assert all(a > 0.9 for a in accs), accs
    # workers ended on the same merged model
    assert abs(accs[0] - accs[1]) < 0.02, accs
    print("flax ASGD through the parameter server OK")


if __name__ == "__main__":
    main()
