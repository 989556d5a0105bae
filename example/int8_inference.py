#!/usr/bin/env python
"""INT8 inference end to end: train fp32, calibrate, deploy quantized.

The reference's quantization examples
(``example/quantization/imagenet_gen_qsym*.py``) follow exactly this
flow: a trained fp32 CNN + a handful of calibration batches → an int8
model whose top-1 matches fp32. Here the int8 Dense/Conv compute runs
as int8 matmul/conv with int32 accumulation — the MXU-native layout —
with BatchNorms folded into the preceding convs and per-channel weight
scales (``mxnet_tpu/contrib/quantization.py``).

    python example/int8_inference.py            # CPU backend
    python example/int8_inference.py --ctx tpu  # real chip
"""
import argparse
import os as _os
import sys as _sys
import time

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(_os.path.realpath(__file__)))))

import numpy as np


def make_batch(rng, n):
    y = rng.randint(0, 4, n)
    x = rng.randn(n, 3, 32, 32).astype("f4") * 0.2
    for i, c in enumerate(y):
        x[i, c % 3, :, :] += 2.0
        x[i, :, : (8 * (c // 3 + 1)), :] += 0.7
    return x, y.astype("f4")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--ctx", default="cpu", choices=["cpu", "tpu"])
    p.add_argument("--train-steps", type=int, default=32)
    p.add_argument("--calib-mode", default="entropy",
                   choices=["naive", "entropy"])
    args = p.parse_args()

    if args.ctx == "cpu":
        _os.environ["JAX_PLATFORMS"] = "cpu"

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.contrib import quantization as q
    from mxnet_tpu.gluon.model_zoo.vision import resnet18_v1

    ctx = mx.tpu() if args.ctx == "tpu" else mx.cpu()
    np.random.seed(0)
    mx.random.seed(0)
    rng = np.random.RandomState(0)

    net = resnet18_v1(classes=4)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 2e-3})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    for step in range(args.train_steps):
        x, y = make_batch(rng, 16)
        with autograd.record():
            loss = loss_fn(net(nd.array(x, ctx=ctx)),
                           nd.array(y, ctx=ctx)).mean()
        loss.backward()
        trainer.step(1)
        if (step + 1) % 8 == 0:
            print(f"step {step + 1}: loss {float(loss.asnumpy()):.3f}")
    # settle BN running stats for a meaningful inference reference
    # (train_mode updates the stats without taping a backward graph)
    for i in range(12):
        with autograd.train_mode():
            net(nd.array(make_batch(rng, 32)[0], ctx=ctx))

    calib = [nd.array(make_batch(rng, 16)[0], ctx=ctx)
             for _ in range(8)]
    qnet = q.quantize_net(net, calib_data=iter(calib),
                          calib_mode=args.calib_mode)
    print(f"quantized {len(qnet.layer_map)} layers "
          f"({args.calib_mode} calibration)")

    xh, yh = make_batch(rng, 64)
    xh = nd.array(xh, ctx=ctx)
    net(xh).wait_to_read()       # warm: compile both paths first,
    qnet(xh).wait_to_read()      # so the timings measure inference
    t0 = time.time()
    fp = net(xh).asnumpy()
    t_fp = time.time() - t0
    t0 = time.time()
    qo = qnet(xh).asnumpy()
    t_q = time.time() - t0
    agree = float((fp.argmax(1) == qo.argmax(1)).mean())
    print(f"fp32 top-1 {float((fp.argmax(1) == yh).mean()):.3f} "
          f"({t_fp * 1e3:.0f} ms)  "
          f"int8 top-1 {float((qo.argmax(1) == yh).mean()):.3f} "
          f"({t_q * 1e3:.0f} ms)  agreement {agree:.3f}")
    assert agree >= 0.95, "int8 must track fp32"
    print("INT8 INFERENCE OK")


if __name__ == "__main__":
    main()
