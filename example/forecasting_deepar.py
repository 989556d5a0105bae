#!/usr/bin/env python
"""Probabilistic forecasting with DeepAR on synthetic seasonal series.

Parity model: GluonTS's DeepAR examples (BASELINE config #4).  Training
is a single hybridized lax.scan program; prediction draws sample paths
and prints empirical P10/P50/P90 quantile coverage.

    python example/forecasting_deepar.py --ctx tpu
    python example/forecasting_deepar.py --steps 30     # CI smoke
"""
import argparse

import numpy as np

import os as _os
import sys as _sys

# run from a plain checkout: make the repo importable
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.models import DeepAR


def synthetic_series(n, length, seed=0):
    rng = np.random.RandomState(seed)
    t = np.arange(length)[None, :]
    phase = rng.rand(n, 1) * 2 * np.pi
    amp = 1.0 + 3.0 * rng.rand(n, 1)
    x = amp * np.sin(2 * np.pi * t / 12.0 + phase)
    return (x + 0.1 * rng.randn(n, length)).astype("float32")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ctx", default="cpu", choices=["cpu", "tpu"])
    ap.add_argument("--context-length", type=int, default=24)
    ap.add_argument("--prediction-length", type=int, default=12)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--num-samples", type=int, default=100)
    args = ap.parse_args()

    ctx = mx.tpu() if args.ctx == "tpu" else mx.cpu()
    C, P = args.context_length, args.prediction_length

    series = synthetic_series(args.batch_size, C + P)
    past = nd.array(series[:, :C], ctx=ctx)
    future = nd.array(series[:, C:], ctx=ctx)

    net = DeepAR(C, P, num_cells=40, num_layers=2)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01})

    for step in range(args.steps):
        with autograd.record():
            loss = net(past, future).mean()
        loss.backward()
        trainer.step(args.batch_size)
        if step % 25 == 0 or step == args.steps - 1:
            print(f"step {step}: nll={float(loss.asnumpy()):.4f}")

    paths = net.sample(past, num_samples=args.num_samples).asnumpy()
    truth = series[:, C:]
    q10, q50, q90 = np.percentile(paths, [10, 50, 90], axis=0)
    coverage = ((truth >= q10) & (truth <= q90)).mean()
    mae_p50 = np.abs(q50 - truth).mean()
    print(f"P10-P90 coverage={coverage:.2%} (target ~80%), "
          f"P50 MAE={mae_p50:.3f}")
    return coverage


if __name__ == "__main__":
    main()
