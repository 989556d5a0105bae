#!/usr/bin/env python
"""MNIST training with Gluon — the framework's "hello world".

Parity model: the reference's ``example/image-classification/
train_mnist.py`` + ``example/gluon/mnist/mnist.py``.  The TPU story is
the one-line context swap: ``--ctx tpu`` is the ONLY change vs CPU
(BASELINE config #1).

Offline environments: pass ``--synthetic`` to train on generated
MNIST-shaped data (the gluon vision datasets' ``synthetic=N`` hook).

    python example/train_mnist.py --ctx tpu --epochs 2
    python example/train_mnist.py --synthetic --epochs 1   # CI smoke
"""
import argparse
import time

import os as _os
import sys as _sys

# run from a plain checkout: make the repo importable
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.data.vision import MNIST, transforms


def build_net():
    net = nn.HybridSequential(prefix="mlp_")
    with net.name_scope():
        net.add(nn.Dense(128, activation="relu"),
                nn.Dense(64, activation="relu"),
                nn.Dense(10))
    return net


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ctx", default="cpu", choices=["cpu", "tpu"])
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--synthetic", action="store_true",
                    help="synthetic MNIST-shaped data (offline/CI)")
    args = ap.parse_args()

    ctx = mx.tpu() if args.ctx == "tpu" else mx.cpu()
    synth = 2048 if args.synthetic else None

    to_tensor = transforms.ToTensor()
    train_ds = MNIST(train=True, synthetic=synth).transform_first(
        to_tensor)
    val_ds = MNIST(train=False, synthetic=synth and 512).transform_first(
        to_tensor)
    train_data = gluon.data.DataLoader(train_ds, args.batch_size,
                                       shuffle=True, num_workers=2)
    val_data = gluon.data.DataLoader(val_ds, args.batch_size,
                                     num_workers=2)

    net = build_net()
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    metric = mx.metric.Accuracy()

    for epoch in range(args.epochs):
        metric.reset()
        tic = time.time()
        for x, y in train_data:
            x, y = x.as_in_context(ctx), y.as_in_context(ctx)
            with autograd.record():
                out = net(x.reshape((x.shape[0], -1)))
                loss = loss_fn(out, y)
            loss.backward()
            trainer.step(x.shape[0])
            metric.update([y], [out])
        name, acc = metric.get()
        print(f"epoch {epoch}: train-{name}={acc:.4f} "
              f"({time.time() - tic:.1f}s)")

    metric.reset()
    for x, y in val_data:
        x, y = x.as_in_context(ctx), y.as_in_context(ctx)
        metric.update([y], [net(x.reshape((x.shape[0], -1)))])
    name, acc = metric.get()
    print(f"validation {name}={acc:.4f}")
    return acc


if __name__ == "__main__":
    main()
