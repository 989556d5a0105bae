#!/usr/bin/env python
"""Multi-process data-parallel training via the kvstore + launcher.

Parity model: the reference's ``example/distributed_training*`` run as
``tools/launch.py -n N --launcher local python train.py --kv-store
dist_sync``.  Each worker computes gradients on its own data shard;
``gluon.Trainer`` wired to the ``dist_tpu_sync`` kvstore aggregates
them across processes (allgather over the JAX distributed runtime —
ps-lite's role) and applies identical updates everywhere.

    JAX_PLATFORMS=cpu python tools/launch.py -n 2 \
        python example/distributed_training.py
"""
import numpy as np

import os as _os
import sys as _sys

# run from a plain checkout: make the repo importable
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd


def main():
    kv = mx.kv.create("dist_tpu_sync")
    rank, nworkers = kv.rank, kv.num_workers
    print(f"worker {rank}/{nworkers} up "
          f"(distributed={kv.is_distributed})")

    # DIFFERENT init per worker on purpose: the dist kvstore broadcasts
    # rank 0's weights at trainer init, so all workers train one model
    mx.random.seed(1234 + rank)
    net = gluon.nn.Dense(1, in_units=8)
    net.initialize(mx.init.Xavier())

    # same dataset everywhere, sharded by rank: worker r takes rows
    # r::nworkers (the reference's part_index/num_parts convention)
    rng = np.random.RandomState(0)
    X = rng.rand(256, 8).astype("f4")
    w_true = rng.rand(8, 1).astype("f4")
    Y = X @ w_true
    Xs = nd.array(X[rank::nworkers])
    Ys = nd.array(Y[rank::nworkers])

    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.5}, kvstore=kv)
    loss_fn = gluon.loss.L2Loss()

    for step in range(150):
        with autograd.record():
            loss = loss_fn(net(Xs), Ys)
        loss.backward()  # per-sample losses: backward sums them
        # step() pushes grads through the kvstore (cross-process sum),
        # normalized by the GLOBAL batch size
        trainer.step(Xs.shape[0] * nworkers)

    final = float(loss.asnumpy().mean())
    print(f"worker {rank}: final loss {final:.6f}")
    assert final < 1e-3, "did not converge"
    return final


if __name__ == "__main__":
    main()
