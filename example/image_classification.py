#!/usr/bin/env python
"""Image classification on synthetic ImageNet-shaped data.

Parity model: the reference's ``example/image-classification/``
(``train_imagenet.py`` with ``--benchmark 1``'s synthetic iterator +
``benchmark_score.py``).  The model is a hybridized model-zoo network:
one whole-graph XLA compile covers forward+backward+update per step
(BASELINE config #2).

    python example/image_classification.py --model resnet50_v1 \
        --ctx tpu --batch-size 64
    python example/image_classification.py --model resnet18_v1 \
        --image-size 64 --batch-size 8 --steps 4      # CI smoke
"""
import argparse
import time

import numpy as np

import os as _os
import sys as _sys

# run from a plain checkout: make the repo importable
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon.model_zoo import vision


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="resnet50_v1",
                    help="any mx.gluon.model_zoo.vision model name")
    ap.add_argument("--ctx", default="cpu", choices=["cpu", "tpu"])
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--classes", type=int, default=1000)
    args = ap.parse_args()

    ctx = mx.tpu() if args.ctx == "tpu" else mx.cpu()
    net = vision.get_model(args.model, classes=args.classes)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.hybridize()

    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9,
                             "wd": 1e-4})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    # synthetic ImageNet batch (the reference's dummy-iter benchmark)
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.rand(args.batch_size, 3, args.image_size,
                             args.image_size).astype("f4"), ctx=ctx)
    y = mx.nd.array(rng.randint(0, args.classes,
                                args.batch_size).astype("f4"), ctx=ctx)

    def step():
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(args.batch_size)
        return loss

    print(f"compiling {args.model} (batch={args.batch_size}, "
          f"image={args.image_size}) ...")
    loss = step()
    loss.wait_to_read()

    tic = time.time()
    for _ in range(args.steps):
        loss = step()
    loss.wait_to_read()
    mx.nd.waitall()
    dt = time.time() - tic
    ips = args.batch_size * args.steps / dt
    print(f"{args.model}: {ips:.1f} images/sec "
          f"(loss={float(loss.asnumpy().mean()):.3f})")
    return ips


if __name__ == "__main__":
    main()
