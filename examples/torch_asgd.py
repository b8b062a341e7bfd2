#!/usr/bin/env python
"""Multi-worker ASGD training of a torch model through the parameter server.

The reference's flagship binding benchmark trains CIFAR-10 ResNet with N
processes doing ASGD through Multiverso's param-manager sync (reference
binding/python/docs/BENCHMARK.md:57-59 and the Theano/Lasagne
MVModelParamManager). Same pattern here, 2026-style: torch (CPU) model,
`TorchParamManager` delta-sync against an ArrayTable, in-process worker
threads standing in for the reference's processes.

Each worker owns a private model replica and a disjoint data shard; every
`sync_freq` batches it pushes (current - last_synced) and pulls the merged
parameters — the reference's delta trick (param_manager.py:67-82). The
workers' replicas converge to one shared model that fits the whole dataset.

Run:  python torch_asgd.py
"""

import threading

import numpy as np

import jax

import torch
import torch.nn as nn

import multiverso_tpu as mv
from multiverso_tpu.binding.param_manager import TorchParamManager

WORKERS, EPOCHS, BATCH, SYNC_FREQ = 2, 30, 64, 4
FEATURES, CLASSES, N = 20, 3, 3000


def make_model():
    torch.manual_seed(7)  # identical init on every worker (master pushes)
    return nn.Sequential(nn.Linear(FEATURES, 64), nn.ReLU(),
                         nn.Linear(64, CLASSES))


def main():
    dev = jax.devices()
    print(f"running on {len(dev)} x {dev[0].platform} ({dev[0].device_kind})")
    rng = np.random.default_rng(0)
    centers = rng.standard_normal((CLASSES, FEATURES)).astype(np.float32) * 2
    y = rng.integers(0, CLASSES, N)
    X = centers[y] + rng.standard_normal((N, FEATURES)).astype(np.float32)
    Xt = torch.from_numpy(X)
    yt = torch.from_numpy(y)

    mv.MV_Init([f"-num_workers={WORKERS}"])
    final_acc = {}

    # ONE shared table for all in-process workers (multi-process jobs
    # instead create one handler per process; table ids align like the
    # reference). Master-initializes from the seeded template model.
    from multiverso_tpu.binding import ArrayTableHandler
    from multiverso_tpu.binding.param_manager import _flatten
    template = make_model()
    init = _flatten([p.detach().numpy() for p in template.parameters()])
    shared = ArrayTableHandler(init.size, init_value=init)

    def worker(wid):
        with mv.MV_WorkerContext(wid):
            model = make_model()
            mgr = TorchParamManager(model, table=shared)
            opt = torch.optim.SGD(model.parameters(), lr=0.05)
            loss_fn = nn.CrossEntropyLoss()
            shard = slice(wid * N // WORKERS, (wid + 1) * N // WORKERS)
            Xs, ys = Xt[shard], yt[shard]
            step = 0
            for _ in range(EPOCHS):
                perm = torch.randperm(len(Xs))
                for start in range(0, len(Xs), BATCH):
                    idx = perm[start:start + BATCH]
                    opt.zero_grad()
                    loss_fn(model(Xs[idx]), ys[idx]).backward()
                    opt.step()
                    step += 1
                    if step % SYNC_FREQ == 0:
                        mgr.sync_all_param()
            mgr.sync_all_param()
            with torch.no_grad():
                acc = (model(Xt).argmax(1) == yt).float().mean().item()
            final_acc[wid] = acc

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(WORKERS)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    # (no MV_Barrier here: it is a NUM_WORKERS-party rendezvous for worker
    # threads; the main thread alone would wait forever)
    mv.MV_ShutDown()
    for wid, acc in sorted(final_acc.items()):
        print(f"worker {wid}: full-dataset accuracy {acc:.3f}")
    assert all(a > 0.9 for a in final_acc.values()), final_acc


if __name__ == "__main__":
    main()
